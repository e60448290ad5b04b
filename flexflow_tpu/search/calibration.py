"""On-device op-cost calibration for the search's cost model.

Reference: the reference times every candidate op on the real device
(Op::measure_operator_cost via inner_measure_operator_cost,
include/flexflow/operator.h:127) and caches the result keyed by op
params + machine view (src/runtime/simulator.cc:588-628).

TPU-native twist (SURVEY §7 hard part 1): XLA fuses aggressively, so a
per-op wall-clock microbenchmark taken in isolation over-charges fusion
boundaries. The primary calibration is therefore *class-level*: a small
suite of representative ops is timed once per device kind, the ratio
measured/analytic-roofline becomes a derate for that op class
(matmul-bound vs memory-bound), and exact per-op measurements are layered
on top when `measure` mode is on. Everything persists to an on-disk JSON
cache keyed by device kind, with factory tables committed under
``calibration_data/`` so searches on known chips are calibrated without
ever touching the device. The committed table is the only one a search
reads unless ``FLEXFLOW_TPU_CACHE`` names a directory: decisions do not
depend on a file outside the checkout.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from ..core.tensor import TensorSpec
from ..core.types import DataType, OpType
from ..obs.steptrace import GLOBAL_STARTUP
from ..ops.base import get_op_def
from ..parallel.machine import MachineSpec, TPUChipSpec

# op classes for derate sharing: FLOPs-dominated ops ride the MXU,
# everything else is HBM-bandwidth-bound
MATMUL_OPS = frozenset(
    {
        OpType.LINEAR,
        OpType.BATCH_MATMUL,
        OpType.MULTIHEAD_ATTENTION,
        OpType.CONV2D,
    }
)


def op_class(op_type: OpType) -> str:
    return "matmul" if op_type in MATMUL_OPS else "memory"


def cost_key(op_type: OpType, params, input_specs: Sequence[TensorSpec], n_parts: int) -> str:
    shapes = ";".join(f"{tuple(s.shape)}:{s.dtype.name}" for s in input_specs)
    return f"{op_type.name}|{params!r}|{shapes}|{n_parts}"


def op_ledger_key(
    device_kind: str, op_type: OpType, params,
    input_specs: Sequence[TensorSpec], n_parts: int,
) -> str:
    """Truth-ledger key for one op signature ON one device kind
    (``op:<device-slug>:<cost_key>``). The device lives in the key so a
    prediction made for a hypothetical machine (a v5e what-if searched
    on a CPU dev box) can never join a measurement taken on different
    hardware and raise a false drift alarm."""
    return f"op:{_slug(device_kind)}:{cost_key(op_type, params, input_specs, n_parts)}"


def detected_device_kind() -> str:
    """The default backend's device kind ("cpu", "TPU v5 lite", ...) —
    the one shared detection used by chip resolution, the truth ledger,
    and the strategy predictor. A backend that fails to initialize
    raises here; it is not answered with "cpu"."""
    import jax

    return jax.devices()[0].device_kind


def mesh_device_kind(kind: str, count: int) -> str:
    """Mesh geometry as a device kind: ``"TPU v5e x4"`` — kind x chip
    count. :func:`chip_spec_for` parses the suffix back into an
    AGGREGATE chip spec (peaks and capacity scaled by the count), so a
    multi-chip serving engine's MFU divides by the mesh's peak FLOPs
    instead of one chip's — a 4-chip engine reporting against a single
    chip would happily claim >100% MFU."""
    if count <= 1:
        return kind
    return f"{kind} x{int(count)}"


@dataclasses.dataclass
class Calibration:
    """Measured timing data for one device kind."""

    device_kind: str = "analytic"
    # class -> multiplier applied to the analytic roofline time
    # (>1 = device slower than roofline; seeded at 1.0 = trust roofline)
    derates: Dict[str, float] = dataclasses.field(default_factory=dict)
    # exact measured seconds per op signature (reference: the
    # hash_to_operator_cost cache, simulator.cc:588-628)
    entries: Dict[str, float] = dataclasses.field(default_factory=dict)
    # suite ops whose measurement never resolved above the jitter floor
    # (cost keys). Persisted so a partial table is LOUD: consumers and
    # the evidence log can see exactly which ops fell back to
    # roofline x derate and which classes the derate geomean missed.
    failed: List[str] = dataclasses.field(default_factory=list)

    def __post_init__(self):
        # where this table came from (file path when loaded from disk):
        # ends the truth ledger's drift-blame string so a stale table is
        # named, not just detected. Plain attribute, not a field — it
        # must not ride to_json into the persisted tables.
        self.source = "(in-memory)"

    def derate(self, op_type: OpType) -> float:
        return self.derates.get(op_class(op_type), 1.0)

    def lookup(self, op_type: OpType, params, input_specs, n_parts: int) -> Optional[float]:
        return self.entries.get(cost_key(op_type, params, input_specs, n_parts))

    # ----------------------------------------------------------- persist
    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=1, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "Calibration":
        d = json.loads(text)
        return cls(
            device_kind=d.get("device_kind", "analytic"),
            derates=dict(d.get("derates", {})),
            entries=dict(d.get("entries", {})),
            failed=list(d.get("failed", [])),
        )

    def save(self, path: Optional[Path] = None) -> Optional[Path]:
        """Write the table to ``path``, or into :func:`cache_dir` when
        that is set; with neither there is nowhere to keep it and
        nothing is written."""
        if path is None:
            base = cache_dir()
            if base is None:
                return None
            path = base / f"opcosts_{_slug(self.device_kind)}.json"
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        tmp.write_text(self.to_json() + "\n")
        tmp.replace(path)
        return path


def _slug(kind: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in kind.lower()).strip("_") or "unknown"


def cache_dir() -> Optional[Path]:
    """Where measured tables are kept outside the checkout: the
    directory ``FLEXFLOW_TPU_CACHE`` names, or nowhere."""
    env = os.environ.get("FLEXFLOW_TPU_CACHE")
    return Path(env) if env else None


_DATA_DIR = Path(__file__).parent / "calibration_data"


def load_calibration(device_kind: str) -> Optional[Calibration]:
    """The committed factory table — preceded by ``FLEXFLOW_TPU_CACHE``
    when that is set."""
    for base in (cache_dir(), _DATA_DIR):
        if base is None:
            continue
        p = base / f"opcosts_{_slug(device_kind)}.json"
        if p.exists():
            try:
                cal = Calibration.from_json(p.read_text())
            except (json.JSONDecodeError, OSError):
                continue
            cal.source = str(p)
            return cal
    return None


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

# (shape, dtype, backend) -> measured baseline-loop PER-ITERATION slope
_BASELINE_CACHE: Dict[tuple, float] = {}
# per-process dispatch/readback floor (seconds); measured once
_DISPATCH_FLOOR: Dict[str, float] = {}


def _readback_floor(backend: str) -> float:
    """Best-case dispatch+scalar-readback round trip for this backend.

    Any subtraction of two wall-clock timings can only resolve op work
    that is LARGE relative to this number, so the loop trip counts below
    are sized against it.
    """
    hit = _DISPATCH_FLOOR.get(backend)
    if hit is not None:
        return hit
    import jax
    import jax.numpy as jnp

    tiny = jax.jit(lambda x: (x * 1.000001).sum())
    x0 = jnp.ones((8,), jnp.float32)
    float(tiny(x0))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        float(tiny(x0))
        best = min(best, time.perf_counter() - t0)
    _DISPATCH_FLOOR[backend] = best
    return best


def measure_lowered_op(
    op_type: OpType,
    params,
    input_specs: Sequence[TensorSpec],
    n_parts: int = 1,
    inner: int = 32,
    reps: int = 3,
    analytic_hint: Optional[float] = None,
    ledger=None,
    ledger_key: Optional[str] = None,
) -> Optional[float]:
    """Jit one shard of the op's lowering on the default device and time
    it (the reference's inner_measure_operator_cost, operator.h:127).

    The cost of one dispatch plus the readback of its result dwarfs the
    microseconds a single op takes, so the op runs inside one
    XLA program (lax.fori_loop with a data dependency through the carry
    so the loop body can't be hoisted) at TWO trip counts, and the
    per-iteration cost is the SLOPE (t_hi - t_lo) / (hi - lo): every
    fixed cost — dispatch, readback, compile-cache lookup — cancels
    exactly. A structurally-matched baseline loop (same perturb-input
    and reduce-output passes, no op) is sloped the same way and
    subtracted so the dependency-plumbing memory passes cancel too.

    ``hi`` is sized from ``analytic_hint`` (the roofline estimate) so the
    op contributes enough device time to resolve against the readback
    jitter; with no hint the loop escalates until the hi/lo difference
    clears the measured floor. The flush is a scalar readback.
    """
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..ops.base import LowerCtx

        op_def = get_op_def(op_type)
        shard_specs = []
        for i, s in enumerate(input_specs):
            shape = list(s.shape)
            if i == 0 and shape and shape[0] % n_parts == 0:
                shape[0] //= n_parts
            shard_specs.append(TensorSpec(tuple(shape), s.dtype))
        rs = np.random.RandomState(0)
        args = [jnp.asarray(rs.randn(*s.shape), s.dtype.jnp) for s in shard_specs]
        wspecs = op_def.weight_specs(params, shard_specs)
        weights = {
            w.name: jnp.asarray(rs.randn(*w.spec.shape) * 0.02, w.spec.dtype.jnp)
            for w in wspecs
        }
        backend = jax.default_backend()
        if not jnp.issubdtype(args[0].dtype, jnp.floating):
            inner = 0  # can't thread the carry through integer inputs

        def note(result: float) -> float:
            # measure side of the truth ledger: joins the cost model's
            # prediction for the same (device, op, params, shapes,
            # n_parts) key so calib_debug / obsreport report error
            # without a private path. Every successful measurement —
            # slope OR single-shot fallback — passes through here
            # ("counted, never dropped"). A measure-mode CostModel
            # passes its own ledger_key so its prediction joins exactly,
            # whatever device naming it predicted under.
            try:
                led = ledger
                if led is None:
                    from ..obs.truth import GLOBAL_LEDGER as led
                key = ledger_key or op_ledger_key(
                    detected_device_kind(),
                    op_type, params, input_specs, n_parts,
                )
                led.measure(key, result)
            except Exception:
                pass
            return result

        # inputs AND weights are runtime jit arguments — closing over
        # them would bake them into the XLA program as literals, letting
        # the compiler constant-fold/pre-transform weights and bias the
        # measured cost vs real execution where weights are buffers
        def run_op(inputs, wts):
            ctx = LowerCtx(training=False, rng=jax.random.key(0), backend=backend)
            outs = op_def.lower(params, inputs, wts, ctx)
            return sum(jnp.sum(o.astype(jnp.float32)) for o in outs)

        if inner == 0:
            # single-shot fallback (integer first input: can't thread the
            # loop carry through it). Dispatches are enqueued async and
            # flushed once, so the measured window is N device executions
            # plus ONE readback round trip — subtract that floor rather
            # than smearing it across the N executions (the floor alone
            # can be orders of magnitude above a small op's true cost)
            jitted = jax.jit(run_op)
            float(jitted(args, weights))
            n = max(reps, 1) * 8
            t0 = time.perf_counter()
            acc = None
            for _ in range(n):
                acc = jitted(args, weights)
            float(acc)
            elapsed = time.perf_counter() - t0
            per = (elapsed - _readback_floor(backend)) / n
            return note(per) if per > 0 else None

        def perturbed(inputs, acc):
            # cheap data dependency: scales with |inputs[0]|, defeats LICM
            return [inputs[0] + (acc * 1e-30).astype(inputs[0].dtype)] + inputs[1:]

        def make_loop(with_op: bool):
            # the trip count is a TRACED argument (fori_loop with a
            # dynamic bound lowers to while_loop), so every trip count
            # this measurement ever needs shares ONE compiled program —
            # each distinct XLA program costs a full compile, which would
            # otherwise dominate the calibration suite's wall clock
            def fn(inputs, wts, trip):
                def body(i, acc):
                    if with_op:
                        return acc + run_op(perturbed(inputs, acc), wts)
                    x = perturbed(inputs, acc)[0]
                    return acc + jnp.sum(x.astype(jnp.float32))

                return jax.lax.fori_loop(0, trip, body, jnp.float32(0.0))

            return jax.jit(fn)

        def timed(jitted, trip: int) -> float:
            t = jnp.int32(trip)
            best = float("inf")
            for _ in range(max(reps, 1)):
                t0 = time.perf_counter()
                float(jitted(args, weights, t))
                best = min(best, time.perf_counter() - t0)
            return best

        # size the trip counts so the op's OWN time across (hi - lo)
        # iterations is large relative to the readback floor; every
        # fixed cost cancels in the slope, but noise on two wall clocks
        # does not
        floor = _readback_floor(backend)
        # capped: with a slow readback floor an uncapped 12x target
        # would balloon every timing run to many seconds;
        # best-of-``reps`` min-filtering already suppresses the jitter
        # the multiple is guarding against
        resolve = min(max(0.25 if backend == "cpu" else 1.0, 12.0 * floor), 4.0)
        # trip cap bounds ITERATIONS, not wall time (hi is sized from
        # resolve/est, <= ~4 s of device time per timing either way). It
        # must be high enough that a ~1 us op can still accumulate
        # enough total signal to clear the jitter-floor acceptance —
        # 2^17 silently dropped BATCH_MATMUL/LAYERNORM/RELU on the v5e
        # (4-6 us/iter tops out at ~0.6 s, under a ~1.2 s acceptance),
        # skewing the class derates toward the big ops
        CAP = 1 << 21

        def adaptive_slope(with_op: bool, est_hint: Optional[float]) -> Optional[float]:
            """Per-iteration slope, or None when it never resolved above
            the jitter floor (wall-clock noise, not a measurement)."""
            jitted = make_loop(with_op)
            lo = max(4, inner // 4)
            float(jitted(args, weights, jnp.int32(lo)))  # compile + warm
            t_lo = timed(jitted, lo)
            # per-iteration estimate for sizing: whichever is LARGER of
            # the analytic hint and what t_lo itself implies (so a hint
            # that under-estimates a slow op can't size a loop that runs
            # for minutes)
            est = max(est_hint or 0.0, (t_lo - floor) / lo, 1e-9)
            hi = max(4 * lo, min(lo + int(resolve / est), CAP))
            t_hi = timed(jitted, hi)
            per = (t_hi - t_lo) / (hi - lo)
            # under-resolved (op invisible at this trip count): escalate,
            # re-sizing from the freshly measured slope
            tries = 0
            while per * (hi - lo) < 0.5 * resolve and hi < CAP and tries < 3:
                lo, t_lo = hi, t_hi
                est = max(per, est, 1e-9)
                hi = min(lo + max(int(resolve / est), 3 * lo), CAP)
                t_hi = timed(jitted, hi)
                per = (t_hi - t_lo) / (hi - lo)
                tries += 1
            # acceptance scales with measured NOISE (the readback
            # floor), not the sizing convenience target: a tiny op that
            # tops out at the trip cap with signal well above the floor
            # is a fine measurement; one buried under readback jitter is
            # not, whatever its sign
            accept = min(0.5 * resolve, max(10.0 * floor, 1e-3))
            if per <= 0 or per * (hi - lo) < accept:
                return None
            return per

        per_iter = adaptive_slope(True, analytic_hint)
        if per_iter is None:
            # never rose above the jitter floor even at the trip-count
            # cap: a failed measurement, not a number — returning it
            # would poison the derate geomean and the on-disk cache
            return None
        # the baseline slope depends only on (shape, dtype, backend) —
        # memoize it so a suite of ops sharing a first-input signature
        # pays its compile+timing once. An unresolved baseline means the
        # plumbing is invisible next to the jitter floor: treat as zero
        # (don't discard the op's own perfectly good measurement).
        base_key = (tuple(args[0].shape), str(args[0].dtype), backend)
        base_per_iter = _BASELINE_CACHE.get(base_key)
        if base_per_iter is None:
            base_per_iter = adaptive_slope(False, None) or 0.0
            _BASELINE_CACHE[base_key] = base_per_iter
        # floor: never let noisy subtraction return <=0; 5% of the loop
        # body is a conservative lower bound for the op itself
        return note(max(per_iter - base_per_iter, 0.05 * per_iter))
    except Exception:
        return None


def default_suite(dtype: DataType = DataType.BFLOAT16) -> List[Tuple[OpType, object, List[TensorSpec]]]:
    """Representative (op, params, inputs) covering both op classes at
    MXU-friendly sizes (the shapes BERT-class models actually run)."""
    from ..ops.attention import MultiHeadAttentionParams
    from ..ops.batch_matmul import BatchMatmulParams
    from ..ops.conv import Conv2DParams
    from ..ops.elementwise import ElementUnaryParams
    from ..ops.embedding import EmbeddingParams
    from ..ops.linear import LinearParams
    from ..ops.norm import LayerNormParams
    from ..ops.softmax import SoftmaxParams

    B, S, H, F = 16, 128, 768, 3072
    x = TensorSpec((B * S, H), dtype)
    seq = TensorSpec((B, S, H), dtype)
    return [
        # vision + embedding coverage (ResNet stage-2-ish conv; BERT
        # vocab-sized gather, integer input -> single-shot path)
        (
            OpType.CONV2D,
            Conv2DParams(out_channels=128, kernel=(3, 3), stride=(1, 1),
                         padding=(1, 1), dtype=dtype),
            [TensorSpec((16, 64, 56, 56), dtype)],
        ),
        (
            OpType.EMBEDDING,
            EmbeddingParams(num_entries=30522, out_dim=H, dtype=dtype),
            [TensorSpec((B, S), DataType.INT32)],
        ),
        (OpType.LINEAR, LinearParams(out_dim=F, use_bias=True, dtype=dtype), [x]),
        (OpType.LINEAR, LinearParams(out_dim=H, use_bias=True, dtype=dtype), [TensorSpec((B * S, F), dtype)]),
        (
            OpType.BATCH_MATMUL,
            BatchMatmulParams(),
            [TensorSpec((B * 12, S, 64), dtype), TensorSpec((B * 12, 64, S), dtype)],
        ),
        (
            OpType.MULTIHEAD_ATTENTION,
            MultiHeadAttentionParams(embed_dim=H, num_heads=12, dtype=dtype),
            [seq, seq, seq],
        ),
        (OpType.LAYERNORM, LayerNormParams(axes=(2,), dtype=dtype), [seq]),
        (OpType.SOFTMAX, SoftmaxParams(axis=-1), [TensorSpec((B * 12, S, S), dtype)]),
        (OpType.RELU, ElementUnaryParams(op=OpType.RELU), [TensorSpec((B * S, F), dtype)]),
        (OpType.GELU, ElementUnaryParams(op=OpType.GELU), [TensorSpec((B * S, F), dtype)]),
    ]


def calibrate(
    machine: Optional[MachineSpec] = None,
    device_kind: Optional[str] = None,
    suite: Optional[Sequence] = None,
    save: bool = True,
) -> Calibration:
    """Run the calibration suite on the current default device and derive
    per-class derates (measured / analytic roofline). Ratios are combined
    per class by geometric mean; exact measurements are kept as entries."""
    import numpy as np

    from .cost_model import CostModel

    if device_kind is None:
        device_kind = detected_device_kind()
    machine = machine or MachineSpec(num_nodes=1, devices_per_node=1, chip=chip_spec_for(device_kind))
    base = CostModel(machine)  # uncalibrated roofline
    cal = Calibration(device_kind=device_kind)
    # ``inner`` only seeds the LOW trip count of the slope measurement
    # (lo = inner // 4); the high trip count is sized adaptively from
    # the readback floor and the analytic hint. Smaller seed on CPU
    # (fallback validation only), where ops are slow and dispatch cheap.
    inner = 8 if device_kind == "cpu" else 32
    ratios: Dict[str, List[float]] = {}
    for op_type, params, specs in suite or default_suite():
        op_def = get_op_def(op_type)
        out_specs = op_def.infer_output_specs(params, list(specs))
        analytic = base._roofline_time(
            *_work_of(op_def, params, specs, out_specs), specs[0].dtype
        )
        if analytic <= 0:
            continue  # degenerate roofline: the ratio would be dropped anyway
        measured = measure_lowered_op(
            op_type, params, specs, inner=inner, analytic_hint=analytic
        )
        if measured is None:
            cal.failed.append(cost_key(op_type, params, specs, 1))
            continue
        cal.entries[cost_key(op_type, params, specs, 1)] = measured
        ratios.setdefault(op_class(op_type), []).append(measured / analytic)
    for cls_name, rs in ratios.items():
        cal.derates[cls_name] = float(np.exp(np.mean(np.log(rs))))
    if save and cal.entries:
        cal.save()
    return cal


def _work_of(op_def, params, input_specs, output_specs) -> Tuple[float, float]:
    c = op_def.cost(params, list(input_specs), list(output_specs))
    return c.flops, c.bytes_accessed


def load_or_calibrate(
    machine: Optional[MachineSpec] = None,
    allow_measure: bool = False,
    device_kind: Optional[str] = None,
) -> Calibration:
    """Resolution order: ``FLEXFLOW_TPU_CACHE`` table (when set) ->
    committed factory table -> live calibration (only when
    allow_measure) -> analytic default.

    ``device_kind`` forces the table key; pass "cpu" to calibrate the CPU
    backend explicitly (the auto-detected path treats CPU as analytic so
    ordinary searches in CPU test runs never pay a measurement suite).
    """
    if device_kind is None:
        import jax

        device_kind = (
            "analytic" if jax.default_backend() == "cpu" else detected_device_kind()
        )
    # which branch resolved it rides on the start-up span that is open
    # around this call (``ff.startup.search.calibrate``, ``.executor``)
    if device_kind == "analytic":
        GLOBAL_STARTUP.annotate(calibration="analytic")
        return Calibration()
    hit = load_calibration(device_kind)
    if hit is not None:
        cached = cache_dir() is not None and Path(hit.source).parent == cache_dir()
        GLOBAL_STARTUP.annotate(calibration="cache_table" if cached else "committed_table")
        return hit
    if allow_measure:
        GLOBAL_STARTUP.annotate(calibration="live_measurement")
        return calibrate(machine, device_kind=device_kind)
    GLOBAL_STARTUP.annotate(calibration="analytic")
    return Calibration(device_kind=device_kind)


# ---------------------------------------------------------------------------
# recalibration from the truth ledger (obs/truth.py)
# ---------------------------------------------------------------------------


def recalibration_suggestions(ledger=None, min_rel_err: float = 0.25) -> List[Dict]:
    """Drifting ``op:*`` ledger entries -> suggested calibration-table
    updates. Each suggestion carries the cost key, the stale predicted
    seconds, the measured p50 that should replace it, and the blame
    string — the "the simulator is lying, now what?" hand-off."""
    if ledger is None:
        from ..obs.truth import GLOBAL_LEDGER as ledger  # noqa: F811
    out: List[Dict] = []
    for e in ledger.report()["entries"]:
        if not e["key"].startswith("op:") or e["pairs"] < ledger.min_samples:
            continue
        parts = e["key"].split(":", 2)  # op:<device-slug>:<cost_key>
        if len(parts) != 3:
            continue
        ewma = e["rel_err_ewma"]
        if ewma is None or abs(ewma) < min_rel_err or e["measured_p50_s"] is None:
            continue
        out.append({
            "device": parts[1],
            "cost_key": parts[2],
            "label": e["label"],
            "predicted_s": e["predicted_s"],
            "measured_p50_s": e["measured_p50_s"],
            "rel_err": ewma,
            "blame": e["last_blame"] or (
                f"{e['label']}: predicted {e['predicted_s']:.3g}s, "
                f"measured p50 {e['measured_p50_s']:.3g}s, error {ewma:+.0%}"
            ),
        })
    return out


def apply_recalibration(
    cal: Calibration,
    suggestions: Optional[Sequence[Dict]] = None,
    ledger=None,
    min_rel_err: float = 0.25,
    save: bool = False,
) -> List[Dict]:
    """Fold measured medians back into ``cal.entries`` for every
    drifting op the ledger has evidence on; returns what was applied.
    ``save=True`` persists the refreshed table to the on-disk cache."""
    applied = [
        s for s in (
            suggestions if suggestions is not None
            else recalibration_suggestions(ledger, min_rel_err)
        )
        # never fold one device's measurements into another device's
        # table (suggestions carry the ledger key's device slug)
        if s.get("device") in (None, _slug(cal.device_kind))
    ]
    for s in applied:
        cal.entries[s["cost_key"]] = s["measured_p50_s"]
    if save and applied:
        try:
            cal.save()
        except OSError:
            pass
    return applied


# ---------------------------------------------------------------------------
# chip presets (peak numbers for detected hardware; bench + cost model)
# ---------------------------------------------------------------------------

_CHIP_PRESETS = {
    "v2": TPUChipSpec(name="v2", bf16_flops=22.5e12, f32_flops=22.5e12, hbm_bandwidth=0.35e12, hbm_capacity=8e9, ici_bandwidth=62.5e9, ici_links=4),
    "v3": TPUChipSpec(name="v3", bf16_flops=61.25e12, f32_flops=61.25e12, hbm_bandwidth=0.45e12, hbm_capacity=16e9, ici_bandwidth=81.25e9, ici_links=4),
    "v4": TPUChipSpec(name="v4", bf16_flops=275e12, f32_flops=137e12, hbm_bandwidth=1.23e12, hbm_capacity=32e9, ici_bandwidth=112.5e9, ici_links=6),
    "v5e": TPUChipSpec(name="v5e", bf16_flops=197e12, f32_flops=98.5e12, hbm_bandwidth=0.82e12, hbm_capacity=16e9, ici_bandwidth=56.25e9, ici_links=4),
    "v5p": TPUChipSpec(name="v5p", bf16_flops=459e12, f32_flops=115e12, hbm_bandwidth=2.76e12, hbm_capacity=95e9, ici_bandwidth=100e9, ici_links=6),
    "v6e": TPUChipSpec(name="v6e", bf16_flops=918e12, f32_flops=459e12, hbm_bandwidth=1.64e12, hbm_capacity=32e9, ici_bandwidth=112.5e9, ici_links=4),
    # CPU backend (honest simulator validation on the fallback path —
    # never compare a TPU roofline against a CPU wall clock): nominal
    # multicore-XLA peaks; the calibration derates correct the rest.
    # ici_*/coll_overhead model XLA host-platform virtual-device
    # collectives: memcpy-grade bandwidth plus a LARGE fixed cost per
    # collective invocation (cross-thread rendezvous).
    # REFITTED in round 5 after two honesty fixes: (a) the bench's
    # tp/hybrid "measurements" had been silently running REPLICATED
    # (strategies built for a different graph never applied — now a
    # compile-time error), and (b) bf16 models had been computing their
    # dense layers in f32. Against honest quiet dp/tp/hybrid bf16 steps
    # the fit is coll_overhead=0.25 with coll_groups_alpha=0 —
    # independent group instances of one collective do NOT serialize on
    # today's XLA host platform (the old x groups assumption came from
    # the replicated fake measurement) — giving ratios dp 0.73 /
    # tp 0.92 / hybrid 1.42 with measured-rank agreement. The pipeline
    # family is deliberately left OUT of the fitting set as a transfer
    # check (bench reports its ratio separately). Expect drift on very
    # different core counts, within the bench's [0.3, 3] band.
    "cpu": TPUChipSpec(name="cpu", bf16_flops=5e10, f32_flops=1e11, hbm_bandwidth=2e10, hbm_capacity=16e9, ici_bandwidth=1e9, ici_links=1, ici_latency=1e-3, coll_overhead=0.25, coll_groups_alpha=0.0),
}

# virtual-device compute scaling for the CPU fallback: N virtual devices
# share one physical machine, so the bench divides per-device peaks by
# N * this factor; fitted jointly with the cpu preset above. The round-5
# value absorbs everything the per-op model can't see on this host
# class — thread-pool sharing across the virtual devices, XLA's bf16
# CPU emulation cost on the ops the class derates don't cover exactly,
# and reshard/fusion effects between ops — fitted against honest quiet
# dp/tp/hybrid bf16 step measurements (the suite's entries themselves
# are bf16, calibration_data/opcosts_cpu.json)
CPU_FITTED_CONTENTION = 5.0


def chip_spec_for(device_kind: str) -> TPUChipSpec:
    kind = device_kind.lower()
    # mesh geometry ("TPU v5e x4", from mesh_device_kind): resolve the
    # per-chip spec, then scale compute/memory peaks by the chip count —
    # the aggregate machine MFU and the serving roofline divide by.
    # Per-link ICI numbers stay per-chip (they do not add up).
    m = re.search(r"\s+x(\d+)$", kind)
    if m is not None:
        n = int(m.group(1))
        base = chip_spec_for(device_kind[: m.start()])
        if n <= 1:
            return base
        return dataclasses.replace(
            base,
            name=f"{base.name} x{n}",
            bf16_flops=base.bf16_flops * n,
            f32_flops=base.f32_flops * n,
            hbm_bandwidth=base.hbm_bandwidth * n,
            hbm_capacity=base.hbm_capacity * n,
        )
    if kind == "cpu":
        return _CHIP_PRESETS["cpu"]
    for sub, spec in (
        ("v6e", _CHIP_PRESETS["v6e"]),
        ("v6 lite", _CHIP_PRESETS["v6e"]),
        ("v6", _CHIP_PRESETS["v6e"]),
        ("v5e", _CHIP_PRESETS["v5e"]),
        ("v5 lite", _CHIP_PRESETS["v5e"]),
        ("v5litepod", _CHIP_PRESETS["v5e"]),
        ("v5p", _CHIP_PRESETS["v5p"]),
        ("v5", _CHIP_PRESETS["v5p"]),
        ("v4", _CHIP_PRESETS["v4"]),
        ("v3", _CHIP_PRESETS["v3"]),
        ("v2", _CHIP_PRESETS["v2"]),
    ):
        if sub in kind:
            return spec
    raise ValueError(
        f"no chip preset for device kind {device_kind!r}: add its peaks to "
        f"_CHIP_PRESETS (an unknown device is an error, not a default)"
    )
