"""Engine (``generation/engine.py``, ``decoder.py::prefill`` with
``last_only``): of the rows the prefill programs ran inside the window (a
bucket's each), the share, in percent, that the cross-decoder's layers were
spared: ``cross_rows_skipped_total / (cross_rows_run_total +
cross_rows_skipped_total)`` of the ``prefill`` section of ``/v2/stats``, its
growth between the window's open and close. A program without the section (no
cross-decoder, or one run over every row and not counted) is not read."""


def read(ctx):
    open_, close = ((ctx.get(k) or {}).get("prefill") for k in ("stats_open", "stats_close"))
    if not open_ or not close:
        return None
    run = close["cross_rows_run_total"] - open_["cross_rows_run_total"]
    skipped = close["cross_rows_skipped_total"] - open_["cross_rows_skipped_total"]
    if run + skipped <= 0:
        return None
    return 100.0 * skipped / (run + skipped)
