"""The serving prefill's attention (PR 39): the streamed form — the Pallas
kernel in interpret mode and its XLA composition — against
``masked_attention`` over window x length x group (PR 42: a group of one,
head-major, and a score width beside another value width); the rule that
picks between materialised and streamed by the call's shapes alone; what
the gate refuses; the counters of ``/v2/stats``; and ``pick_block``'s error."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from flexflow_tpu.ops import attention  # noqa: E402
from flexflow_tpu.ops.kernels import flash_attention as fa  # noqa: E402

D = 128


def operands(seq, heads, kv_heads, seed=0, batch=2, dtype=jnp.float32, widths=(D, D)):
    """q, k at the score's width (``widths[0]``) and v at the value's."""
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (batch, seq, heads, widths[0]), dtype), jax.random.normal(kk, (batch, seq, kv_heads, widths[0]), dtype),
            jax.random.normal(kv, (batch, seq, kv_heads, widths[1]), dtype))


def live(x, lens):
    """``x`` with the rows past each sequence's length zeroed: a padded
    query's result is nobody's."""
    return jnp.where((jnp.arange(x.shape[1])[None, :] < lens[:, None])[:, :, None, None], x, 0.0)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks a 256-position test walks several of (the chip's are 1,024
    query rows by 512 keys)."""
    monkeypatch.setattr(fa, "STREAM_QUERY_ROWS", 128)
    monkeypatch.setattr(fa, "STREAM_BLOCK_K", 128)


CASES = [
    # seq, heads, kv_heads, window, lengths
    (256, 16, 1, 0, (256, 179)),     # group 16, a full layer
    (256, 16, 1, 100, (256, 179)),   # a window inside the sequence, not a multiple of any block
    (256, 16, 1, 300, (256, 40)),    # a window longer than the sequence; a length inside the first block
    (256, 32, 2, 128, (200, 256)),   # two K/V heads of 16 query heads each, a window of one block
    (512, 8, 1, 130, (512, 333)),    # group 8, four key blocks
    (256, 8, 8, 64, (256, 129)),     # group 1: every head has K/V of its own (the kernel takes it head-major)
]
# a latent layer's expanded form: one query head a K/V head, scores at 192 beside values of 128
LATENT_CASES = [
    # seq, heads, lengths
    (256, 4, (256, 179)),   # two query blocks of 128 positions, a length short of the bucket
    (512, 2, (333, 1)),     # four key blocks; ONE live position
    (256, 64, (200,)),      # the cell's 64 heads
]


@pytest.mark.parametrize("seq,heads,kv_heads,window,lens", CASES)
def test_the_xla_composition_is_masked_attention(seq, heads, kv_heads, window, lens):
    q, k, v = operands(seq, heads, kv_heads)
    lens = jnp.asarray(lens, jnp.int32)
    want = attention.masked_attention(q, k, v, lens, causal=True, window=window)
    for chunk in (64, None):
        got = fa.reference_prefill_stream_attention(q, k, v, lens, window=window, chunk=chunk)
        np.testing.assert_allclose(np.asarray(live(got, lens)), np.asarray(live(want, lens)), atol=2e-6)


@pytest.mark.parametrize("seq,heads,kv_heads,window,lens", CASES[:5])
def test_the_kernel_interpreted_is_masked_attention(small_blocks, seq, heads, kv_heads, window, lens):
    q, k, v = operands(seq, heads, kv_heads, seed=1)
    lens = jnp.asarray(lens, jnp.int32)
    assert fa.prefill_stream_refusal(q.shape, k.shape, 4) is None
    want = attention.masked_attention(q, k, v, lens, causal=True, window=window)
    got = fa.prefill_stream_attention(q, k, v, lens, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(live(got, lens)), np.asarray(live(want, lens)), atol=2e-6)


def test_the_kernel_interpreted_in_bfloat16_lies_within_its_roundings(small_blocks):
    q, k, v = operands(256, 16, 1, seed=2, dtype=jnp.bfloat16)
    lens = jnp.asarray([256, 101], jnp.int32)
    want = attention.masked_attention(*(a.astype(jnp.float32) for a in (q, k, v)), lens, causal=True, window=90)
    got = fa.prefill_stream_attention(q, k, v, lens, window=90, interpret=True).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(live(got - want, lens)))) < 0.05


def test_the_kernel_interpreted_at_group_one_is_masked_attention(small_blocks):
    """The last of :data:`CASES` (8 heads of 128 over 8 K/V heads, a window):
    the gate takes it since PR 42, head-major."""
    seq, heads, kv_heads, window, lens = CASES[5]
    q, k, v = operands(seq, heads, kv_heads, seed=1)
    lens = jnp.asarray(lens, jnp.int32)
    assert fa.prefill_stream_refusal(q.shape, k.shape, 4) is None and fa.stream_blocks(seq, 1) == (128, 128)
    want = attention.masked_attention(q, k, v, lens, causal=True, window=window)
    got = fa.prefill_stream_attention(q, k, v, lens, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(live(got, lens)), np.asarray(live(want, lens)), atol=2e-6)


@pytest.mark.parametrize("seq,heads,lens", LATENT_CASES)
def test_the_kernel_interpreted_at_two_widths_is_masked_attention(small_blocks, seq, heads, lens):
    """Score width 192 beside value width 128 at one query head a K/V
    head: the result has the value's width, the scale is of the score's."""
    q, k, v = operands(seq, heads, heads, seed=4, batch=len(lens), widths=(192, 128))
    lens = jnp.asarray(lens, jnp.int32)
    assert fa.prefill_stream_refusal(q.shape, k.shape, 4, v.shape) is None
    want = attention.masked_attention(q, k, v, lens, causal=True)
    got = fa.prefill_stream_attention(q, k, v, lens, interpret=True)
    assert got.shape == want.shape == (len(lens), seq, heads, 128)
    np.testing.assert_allclose(np.asarray(live(got, lens)), np.asarray(live(want, lens)), atol=2e-6)


def test_the_kernel_interpreted_at_two_widths_in_bfloat16_lies_within_its_roundings(small_blocks):
    q, k, v = operands(256, 4, 4, seed=5, dtype=jnp.bfloat16, widths=(192, 128))
    lens = jnp.asarray([256, 101], jnp.int32)
    want = attention.masked_attention(*(a.astype(jnp.float32) for a in (q, k, v)), lens, causal=True)
    got = fa.prefill_stream_attention(q, k, v, lens, interpret=True)
    assert got.dtype == jnp.bfloat16 and float(jnp.max(jnp.abs(live(got.astype(jnp.float32) - want, lens)))) < 0.05


def test_a_group_of_sixteen_scores_at_another_width_than_it_weighs(small_blocks):
    """The two widths are the grouped layout's too (no configuration has
    the shape; the rule asks shapes, so the kernel holds what it admits)."""
    q, k, v = operands(256, 16, 1, seed=6, widths=(192, 128))
    lens = jnp.asarray([256, 77], jnp.int32)
    assert fa.prefill_stream_refusal(q.shape, k.shape, 4, v.shape) is None
    want = attention.masked_attention(q, k, v, lens, causal=True, window=100)
    got = fa.prefill_stream_attention(q, k, v, lens, window=100, interpret=True)
    np.testing.assert_allclose(np.asarray(live(got, lens)), np.asarray(live(want, lens)), atol=2e-6)


def test_the_gate_says_why_it_refuses():
    ok = ((1, 6144, 128, 128), (1, 6144, 8, 128))
    assert fa.prefill_stream_refusal(*ok, 2) is None and fa.stream_blocks(6144, 16) == (64, 512)
    assert fa.stream_blocks(5120, 16) == (64, 512)
    assert "no whole tile" in fa.prefill_stream_refusal((1, 6144, 32, 128), (1, 6144, 8, 128), 2)  # group 4 in bfloat16
    assert "lanes" in fa.prefill_stream_refusal((1, 6144, 128, 64), (1, 6144, 8, 64), 2)
    assert "divide" in fa.prefill_stream_refusal((1, 6100, 128, 128), (1, 6100, 8, 128), 2)
    assert "VMEM" in fa.prefill_stream_refusal((1, 65536, 128, 128), (1, 65536, 8, 128), 2)


LATENT = ((1, 4096, 64, 192), (1, 4096, 64, 192), 2, (1, 4096, 64, 128))  # the LongCat call: q, k, itemsize, v


@pytest.mark.parametrize("q,k,itemsize,v,why", [
    (*LATENT, None),                                                                       # score 192 / value 128 at group 1
    ((1, 3072, 64, 192), (1, 3072, 64, 192), 2, (1, 3072, 64, 128), None),                 # the cell's other bucket
    ((1, 4096, 64, 128), (1, 4096, 64, 128), 2, None, None),                               # plain multi-head of 128
    ((1, 4096, 128, 192), (1, 4096, 8, 192), 2, (1, 4096, 8, 128), None),                  # two widths at group 16
    ((1, 4096, 32, 128), (1, 4096, 8, 128), 2, None, "a group of 4 query heads is no whole tile of 16 rows"),
    ((1, 4096, 32, 128), (1, 4096, 4, 128), 2, None, "a group of 8 query heads is no whole tile of 16 rows"),  # Mellum2's
    ((1, 4096, 32, 128), (1, 4096, 4, 128), 4, None, None),                                # ... a whole tile in float32
    ((1, 4096, 64, 64), (1, 4096, 64, 64), 2, None, "head_dim 64 does not fill the 128 lanes"),
    ((1, 4096, 64, 160), (1, 4096, 64, 160), 2, (1, 4096, 64, 128), "head_dim 160 does not fill"),
    ((1, 4096, 64, 192), (1, 4096, 64, 192), 2, None, "value width 192 does not fill the 128 lanes"),  # v at k's width
    ((1, 4096, 64, 192), (1, 4096, 64, 192), 2, (1, 4096, 64, 64), "value width 64 does not fill"),
    ((1, 4100, 64, 192), (1, 4100, 64, 192), 2, (1, 4100, 64, 128), "does not divide"),
    ((1, 4096, 64, 192), (1, 4096, 32, 192), 2, (1, 4096, 32, 128), "a group of 2 query heads"),
    ((1, 4096, 64, 192), (1, 4096, 48, 192), 2, (1, 4096, 48, 128), "64 query heads over 48 K/V heads"),
    # K and V of one head, twice: 2 x S x (256 + 128 lanes) x 2 bytes against 24 MiB ...
    ((1, 16384, 64, 192), (1, 16384, 64, 192), 2, (1, 16384, 64, 128), None),              # 24 MiB exactly
    ((1, 32768, 64, 192), (1, 32768, 64, 192), 2, (1, 32768, 64, 128), "pass 24 MiB of VMEM"),
    ((1, 24576, 128, 128), (1, 24576, 8, 128), 2, None, None),                             # ... which 128 + 128 lanes fill at 24 k
    ((1, 32768, 128, 128), (1, 32768, 8, 128), 2, None, "pass 24 MiB of VMEM"),
])
def test_the_gate_by_group_widths_and_bytes(q, k, itemsize, v, why):
    """What :func:`prefill_stream_refusal` takes since PR 42 (a group of
    one, a score width in steps of 64 from 128, a value width of its own)
    and every refusal that remains, by its words."""
    got = fa.prefill_stream_refusal(q, k, itemsize, v)
    assert (got is None) if why is None else (got is not None and why in got), got


# the four accepted serving configurations' largest prefill calls (heads, K/V heads, bucket), and this PR's
ACCEPTED = {"gpt2-medium": (16, 16, 1024), "lfm2-8b-a1b": (32, 8, 512), "mellum2-12b": (32, 4, 2048)}


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_the_rule_leaves_the_accepted_configurations_prefills_as_they_were(name, monkeypatch):
    """Their float32 scores stay under the bound (at most 537 MB a call), so
    their programs hold ``masked_attention`` letter for letter, on the chip
    too."""
    heads, kv_heads, bucket = ACCEPTED[name]
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    low = attention.prefill_call_lowering((1, bucket, heads, 128), (1, bucket, kv_heads, 128), 2, backend="tpu")
    assert 4 * heads * bucket * bucket <= 537e6 < attention.STREAM_SCORE_BYTES
    assert low == {"form": "materialised", "kernel": "masked_attention", "refused": None}


@pytest.mark.parametrize("name,head_dim,why", [("gpt2-medium", 64, "128 lanes"), ("lfm2-8b-a1b", 64, "128 lanes"),
                                               ("mellum2-12b", 128, "a group of 8 query heads")])
def test_the_streamed_kernel_takes_none_of_the_accepted_configurations_shapes(name, head_dim, why):
    """Why the bound stands between the two forms on the chip: the Pallas
    call wants a head of 128 lanes and a whole tile of 16 query heads a
    K/V head (or ONE: PR 42), which none of the older three have. What
    they could stream through is the XLA chunk scan, which
    ``chip_smoke.py --group16`` times against ``masked_attention`` at their
    shapes (``MATERIALISED_CALLS``; the readings are in PERF.md section 6)."""
    import chip_smoke

    heads, kv_heads, bucket = ACCEPTED[name]
    assert why in fa.prefill_stream_refusal((1, bucket, heads, head_dim), (1, bucket, kv_heads, head_dim), 2)
    timed = [c for c in chip_smoke.MATERIALISED_CALLS.values() if (c["heads"], c["kv_heads"], c["head_dim"], c["seq"]) == (heads, kv_heads, head_dim, bucket)]
    assert timed and all(4 * c["heads"] * c["seq"] ** 2 <= attention.STREAM_SCORE_BYTES for c in timed)


def test_the_rule_streams_a_call_past_the_bound_and_never_materialises_it(monkeypatch):
    shapes = ((1, 5120, 128, 128), (1, 5120, 8, 128))
    assert 4 * 128 * 5120 * 5120 > 13.4e9
    assert attention.prefill_call_lowering(*shapes, 2, backend="cpu") == {"form": "streamed", "kernel": "xla_chunks", "refused": None}
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    assert attention.prefill_call_lowering(*shapes, 2, backend="tpu")["kernel"] == "prefill_stream_attention"
    refused = attention.prefill_call_lowering((1, 8192, 32, 128), (1, 8192, 8, 128), 2, backend="tpu")
    assert refused["form"] == "streamed" and refused["kernel"] == "xla_chunks" and "tile" in refused["refused"]
    # the value's width is the gate's to ask: the same q and k, streamed by the kernel or by the chunks
    q, k, itemsize, v = LATENT
    assert attention.prefill_call_lowering(q, k, itemsize, "tpu", v_shape=v)["kernel"] == "prefill_stream_attention"
    assert "value width 192" in attention.prefill_call_lowering(q, k, itemsize, "tpu")["refused"]


def test_prefill_attention_dispatches_by_the_bound(monkeypatch):
    """Under the bound the call IS ``masked_attention``; past it (the bound
    lowered for the test) the streamed composition, and the same result."""
    q, k, v = operands(128, 8, 2, seed=3)
    lens = jnp.asarray([128, 77], jnp.int32)
    want = attention.masked_attention(q, k, v, lens, causal=True, window=50)
    np.testing.assert_array_equal(np.asarray(attention.prefill_attention(q, k, v, lens, window=50)), np.asarray(want))
    monkeypatch.setattr(attention, "STREAM_SCORE_BYTES", 1 << 10)
    streamed = attention.prefill_attention(q, k, v, lens, window=50)
    np.testing.assert_allclose(np.asarray(live(streamed, lens)), np.asarray(live(want, lens)), atol=2e-6)


def test_the_engine_counts_streamed_prefills_and_turns_prefix_reuse_off_at_construction(monkeypatch):
    """With the bound lowered a small engine's prefills take the streamed
    form, its tokens stay the materialised engine's and ``/v2/stats``
    counts the calls under ``streamed``. A hit's suffix prefill has no
    streamed form: where even the smallest bucket's scores would pass the
    bound the engine decides ONCE, at construction, keeps no prefix index
    (nothing inserted, nothing matched) and names the refusal."""
    from flexflow_tpu.generation import GenerationEngine, init_decoder_params
    from flexflow_tpu.generation import engine as engine_module
    from flexflow_tpu.generation.decoder import DecoderConfig
    from flexflow_tpu.generation.engine import SamplingParams

    cfg = DecoderConfig(num_layers=2, hidden_size=64, num_heads=4, ff_size=128, seq_length=128, vocab_size=97, causal=True,
                        positions="rotary", num_kv_heads=2, head_dim=16)
    params = init_decoder_params(jax.random.key(0), cfg)
    prompt = [int(t) for t in np.random.RandomState(0).randint(0, 97, size=40)]
    make = lambda: GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[64], max_seq_len=128)  # noqa: E731
    plain = make()
    want = plain.generate([prompt], SamplingParams(max_new_tokens=8))[0]
    follow = plain.generate([prompt + [5, 6, 7]], SamplingParams(max_new_tokens=4))[0]  # its first 40 tokens are cached
    stats = plain.prefill_attention_stats()
    assert stats["materialised_calls_total"] == 2 and stats["prefix_reuse_refused"] is None and "prefix_reuse" not in plain.unsupported
    assert plain.prefix_cache.enabled and plain.prefix_cache.hits == 1 and any(k.startswith("prefix_prefill") for k in plain.trace_counts)
    monkeypatch.setattr(attention, "STREAM_SCORE_BYTES", 1 << 10)
    monkeypatch.setattr(engine_module, "STREAM_SCORE_BYTES", 1 << 10)
    eng = make()
    assert eng.prefill_attention_stats()["programs"]["prefill[64]"] == {"form": "streamed", "kernel": "xla_chunks", "refused": None}
    assert not eng.prefix_cache.enabled and "suffix prefill[64] (the smallest bucket)" in eng.unsupported["prefix_reuse"]
    assert eng.generate([prompt], SamplingParams(max_new_tokens=8))[0] == want
    assert eng.generate([prompt + [5, 6, 7]], SamplingParams(max_new_tokens=4))[0] == follow  # prefilled whole
    stats = eng.prefill_attention_stats()
    assert stats["streamed_calls_total"] == 4 and stats["materialised_calls_total"] == 0 and stats["tokens_total"] == 83
    assert stats["prefix_reuse_refused"] == eng.unsupported["prefix_reuse"] and not stats["refused_total"]
    assert eng.step_counts["prefill"] == 2 and not any(k.startswith("prefix_prefill") for k in eng.trace_counts)
    assert eng.prefix_cache.hits == 0 and eng.prefix_cache.resident_blocks == 0  # no index is kept


@pytest.mark.parametrize("cell", ["gpt2-medium.chat-steady", "gpt2-medium.prompt-batch", "lfm2-8b-a1b.gen-batch",
                                  "mellum2-12b.code-gen", "joyai-llm-flash.long-gen"])
def test_the_accepted_served_cells_keep_their_prefix_reuse(cell):
    """The rule that turns prefix reuse off asks the SMALLEST bucket's
    suffix-prefill scores: every accepted served cell's lie under the
    bound (the five deployments' engines keep their index and serve their
    hits as before), the long-document cell's lie far over it."""
    from benchmark import spec

    def smallest_suffix_scores(name):
        c = spec.load_cell(name)
        d, config = c.workload["deployment"], c.config
        heads = config.get("num_attention_heads") or config.get("n_head") or config["num_heads"]
        return 4 * int(heads) * min(int(b) for b in d["prompt_buckets"]) * int(d["max_seq_len"])

    assert smallest_suffix_scores(cell) <= attention.STREAM_SCORE_BYTES
    assert smallest_suffix_scores("command-a-plus.long-doc") > 16 * attention.STREAM_SCORE_BYTES


def test_pick_block_refuses_an_override_that_does_not_divide():
    assert fa.pick_block(512, None) == 512 and fa.pick_block(512, 128) == 128 and fa.pick_block(100, 256) == 100
    with pytest.raises(ValueError, match="does not divide the sequence length 512"):
        fa.pick_block(512, 96)
