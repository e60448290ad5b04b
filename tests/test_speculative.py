"""Speculative decoding subsystem tests.

Acceptance criteria covered (ISSUE 3):
  * exactness: speculative greedy decode is token-for-token identical to
    the non-speculative engine on 3 model configs, across prefill-bucket
    AND KV-block boundaries, with either drafter
  * the chunked-append (verify) forward reproduces sequential decode
    steps' tokens, and the generalized Pallas paged kernel matches the
    XLA reference in interpret mode
  * trace counters prove the ONE fixed-shape verify jit never recompiles
    at steady state, whatever adaptive k / batch composition does
  * rejection sampling preserves the target distribution (statistical),
    and a zero-draft verify samples bit-identically to a decode step
  * scheduler properties: mid-window EOS, preemption-with-speculation
    exactness, partial-acceptance block accounting (allocator drains to
    empty), adaptive-k shrink/grow
  * chaos through the new ``generation.verify`` fault site; speculation
    counters on /v2/stats and the HTTP ``speculation`` request block
"""
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.generation import (
    ContinuousBatchingScheduler,
    GenerationEngine,
    NgramDrafter,
    SamplingParams,
    SpeculationConfig,
    init_decoder_params,
)
from flexflow_tpu.generation.speculative import (
    DraftModelDrafter,
    rejection_sample,
    speculative_accept,
)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime.faults import FaultInjected, FaultPlan, TransientDeviceError
from flexflow_tpu.serving import RetryPolicy

from conftest import assert_blocks_conserved  # noqa: E402

pytestmark = pytest.mark.speculative

CFG = TransformerConfig(
    num_layers=2, hidden_size=32, num_heads=4, ff_size=64,
    seq_length=64, vocab_size=50, causal=True,
)
# two more shapes for the 3-model exactness criterion
CFG_B = TransformerConfig(
    num_layers=1, hidden_size=48, num_heads=3, ff_size=96,
    seq_length=64, vocab_size=97, causal=True,
)
CFG_C = TransformerConfig(
    num_layers=3, hidden_size=64, num_heads=8, ff_size=128,
    seq_length=64, vocab_size=31, causal=True,
)
BUCKETS = (8, 16, 32, 64)
BLOCK = 8


@pytest.fixture(scope="module")
def decoder_params():
    return init_decoder_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def plain_engine(decoder_params):
    """Shared non-speculative engine: jit traces amortize across the
    module's parity baselines."""
    return GenerationEngine(
        decoder_params, CFG, max_batch_slots=3, block_size=BLOCK,
        prompt_buckets=BUCKETS, max_spec_tokens=4,
    )


@pytest.fixture(scope="module")
def spec_engine(decoder_params):
    """Shared speculating engine (callers attach their own scheduler per
    generate call; the allocator drains between tests)."""
    return GenerationEngine(
        decoder_params, CFG, max_batch_slots=3, block_size=BLOCK,
        prompt_buckets=BUCKETS, max_spec_tokens=4,
    )


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    assert faults.active_plan() is None, "a test leaked an installed FaultPlan"


def make_engine(params=None, cfg=CFG, slots=3, block=BLOCK, spec_k=4, **kw):
    if params is None:
        params = init_decoder_params(jax.random.key(0), cfg)
    return GenerationEngine(
        params, cfg, max_batch_slots=slots, block_size=block,
        prompt_buckets=BUCKETS, max_spec_tokens=spec_k, **kw
    )


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------


def test_ngram_drafter_prompt_lookup():
    d = NgramDrafter(max_ngram=3, min_ngram=1)
    # trailing [1, 2] matched at its most recent earlier occurrence,
    # proposing the continuation [3, 4, 5]
    assert d.propose([1, 2, 3, 4, 5, 9, 1, 2], 3) == [3, 4, 5]
    # most RECENT match wins: ...1,2,7... comes after ...1,2,3...
    assert d.propose([1, 2, 3, 1, 2, 7, 8, 1, 2], 2) == [7, 8]
    # miss -> no proposal (never a wrong-length guess)
    assert d.propose([1, 2, 3, 4, 5, 6], 4) == []
    assert d.propose([7], 4) == []
    # purity: same prefix, same proposal (continuation runs to the end
    # of the matched occurrence's tail, no wrap-around)
    p = [4, 4, 2, 4, 4, 2, 4, 4]
    assert d.propose(p, 4) == d.propose(p, 4) == [2, 4, 4]


def test_draft_model_drafter_greedy_and_pure(decoder_params):
    d = DraftModelDrafter(decoder_params, max_seq_len=64, buckets=BUCKETS)
    out = d.propose([1, 2, 3], 3)
    assert len(out) == 3
    assert d.propose([1, 2, 3], 3) == out  # pure function of the prefix
    # matches the model's own greedy continuation
    from flexflow_tpu.generation import forward_full
    seq = [1, 2, 3]
    for t in out:
        logits = forward_full(decoder_params, jnp.asarray([seq], jnp.int32))
        assert t == int(jnp.argmax(logits[0, -1]))
        seq.append(t)


def test_speculation_config_validation():
    with pytest.raises(ValueError):
        SpeculationConfig(k=0)
    with pytest.raises(ValueError):
        SpeculationConfig(method="tea-leaves")
    with pytest.raises(ValueError):
        SpeculationConfig(min_ngram=3, max_ngram=2)


# ---------------------------------------------------------------------------
# chunked-append attention kernel
# ---------------------------------------------------------------------------


def test_pallas_append_kernel_matches_reference():
    """Interpret-mode parity of the generalized (q_len = W) paged kernel
    against the XLA reference, padding queries included."""
    from flexflow_tpu.ops.kernels.decode_attention import (
        paged_append_attention,
        reference_paged_append_attention,
    )

    rs = np.random.RandomState(3)
    b, w, h, d, nb, bs, mb = 3, 5, 4, 64, 9, 8, 4
    q = jnp.asarray(rs.randn(b, w, h, d), jnp.float32)
    kc = jnp.asarray(rs.randn(2, nb, bs, h, d), jnp.float32)  # [L, nb, bs, H, D]
    vc = jnp.asarray(rs.randn(2, nb, bs, h, d), jnp.float32)
    bt = jnp.asarray(rs.randint(1, nb, (b, mb)), jnp.int32)
    qp = jnp.asarray(
        [[10, 11, 12, 13, 14], [3, 4, -1, -1, -1], [-1, -1, -1, -1, -1]], jnp.int32
    )
    ref = reference_paged_append_attention(q, kc, vc, 1, bt, qp)
    ker = paged_append_attention(q, kc, vc, 1, bt, qp, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker), atol=2e-5)
    # padding queries emit zeros, not NaN
    assert float(jnp.max(jnp.abs(ref[2]))) == 0.0
    assert float(jnp.max(jnp.abs(ker[1, 2:]))) == 0.0


# ---------------------------------------------------------------------------
# verify-step exactness against sequential decode
# ---------------------------------------------------------------------------


def _snapshot(engine):
    return engine.cache.k, engine.cache.v


def _restore(engine, snap):
    engine.cache.k, engine.cache.v = snap


def _decode_one(engine, token, position, blocks, sampling, count):
    """One decode step for slot 0. ``count`` is the generated-token
    count the in-jit key derivation folds (ISSUE 13: the engine derives
    fold_in(key(seed), count) itself — bit-identical to the host keys
    these tests used to build)."""
    tokens = np.zeros((engine.max_batch_slots,), np.int32)
    positions = np.zeros((engine.max_batch_slots,), np.int32)
    tables = np.zeros((engine.max_batch_slots, engine.max_blocks_per_seq), np.int32)
    active = np.zeros((engine.max_batch_slots,), bool)
    temps = np.zeros((engine.max_batch_slots,), np.float32)
    top_ks = np.zeros((engine.max_batch_slots,), np.int32)
    seeds = np.zeros((engine.max_batch_slots,), np.uint32)
    counts = np.zeros((engine.max_batch_slots,), np.int32)
    tokens[0], positions[0], active[0] = token, position, True
    tables[0, : len(blocks)] = blocks
    temps[0], top_ks[0] = sampling.temperature, sampling.top_k
    seeds[0], counts[0] = sampling.seed, count
    return int(
        engine.decode(
            tokens, positions, tables, active, temps, top_ks, seeds, counts
        )[0]
    )


def _verify_one(engine, window, start, n_draft, blocks, sampling, count):
    """One verify step for slot 0; window key j folds count + j in-jit
    (the same per-emitted-count indexing the host key rows carried)."""
    b, w = engine.max_batch_slots, engine.spec_window
    wt = np.zeros((b, w), np.int32)
    st = np.zeros((b,), np.int32)
    nd = np.full((b,), -1, np.int32)
    tables = np.zeros((b, engine.max_blocks_per_seq), np.int32)
    temps = np.zeros((b,), np.float32)
    top_ks = np.zeros((b,), np.int32)
    seeds = np.zeros((b,), np.uint32)
    counts = np.zeros((b,), np.int32)
    wt[0, : len(window)] = window
    st[0], nd[0] = start, n_draft
    tables[0, : len(blocks)] = blocks
    temps[0], top_ks[0] = sampling.temperature, sampling.top_k
    seeds[0], counts[0] = sampling.seed, count
    out, n_em = engine.verify(wt, st, nd, tables, temps, top_ks, seeds, counts)
    return [int(t) for t in out[0, : int(n_em[0])]]


@pytest.fixture(scope="module")
def whitebox_engine(decoder_params):
    """Private engine for the snapshot/restore white-box tests (they
    allocate blocks by hand and never return them)."""
    return make_engine(decoder_params)


def test_verify_window_matches_sequential_decode(whitebox_engine):
    """White box: one greedy verify call over [last, d1, d2] with
    correct drafts emits exactly the 3 tokens that 3 sequential decode
    steps produce. (Temperature mode intentionally has no such
    guarantee per-draft — rejection may legitimately resample — so its
    exactness properties are the zero-draft and distribution tests.)"""
    engine = whitebox_engine
    sampling = SamplingParams(temperature=0.0, seed=11)
    base = jax.random.key(sampling.seed)
    prompt = [1, 2, 3, 4, 5]
    blocks = engine.allocator.allocate(engine.cache_config.blocks_for(len(prompt) + 4))
    t0 = engine.prefill_one(prompt, blocks, sampling, jax.random.fold_in(base, 0))
    snap = _snapshot(engine)
    # sequential: three decode steps with per-count keys 1, 2, 3
    seq = []
    tok, pos = t0, len(prompt)
    for n in (1, 2, 3):
        tok = _decode_one(engine, tok, pos, blocks, sampling, n)
        seq.append(tok)
        pos += 1
    _restore(engine, snap)
    # speculative: drafts ARE the sequential continuation -> all accepted
    out = _verify_one(
        engine, [t0, seq[0], seq[1]], len(prompt), 2, blocks, sampling, 1
    )
    assert out == seq, f"verify {out} != sequential {seq}"


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_zero_draft_verify_samples_like_decode(whitebox_engine, temperature):
    """A zero-draft verify window is bit-identical to a decode step —
    the property that lets plain and speculative requests mix in one
    batch (and mode switches stay replay-deterministic)."""
    engine = whitebox_engine
    sampling = SamplingParams(temperature=temperature, seed=5)
    base = jax.random.key(sampling.seed)
    prompt = [9, 8, 7, 6]
    blocks = engine.allocator.allocate(engine.cache_config.blocks_for(len(prompt) + 2))
    t0 = engine.prefill_one(prompt, blocks, sampling, jax.random.fold_in(base, 0))
    snap = _snapshot(engine)
    via_decode = _decode_one(engine, t0, len(prompt), blocks, sampling, 1)
    _restore(engine, snap)
    via_verify = _verify_one(engine, [t0], len(prompt), 0, blocks, sampling, 1)
    assert via_verify == [via_decode]


# ---------------------------------------------------------------------------
# end-to-end greedy exactness (3 models, bucket + block boundaries)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [CFG, CFG_B, CFG_C], ids=["cfg_a", "cfg_b", "cfg_c"])
def test_greedy_parity_across_models(cfg):
    """Speculative greedy == non-speculative greedy, token-for-token.
    Prompts straddle the 8/16/32 bucket edges; max_new crosses several
    BLOCK-sized cache blocks; block_size 4 forces windows across block
    boundaries constantly."""
    params = init_decoder_params(jax.random.key(1), cfg)
    prompts = [[1, 2, 3, 1, 2, 3, 1], [4] * 8, list(range(2, 19)), [7, 7, 7]]
    prompts = [[t % cfg.vocab_size for t in p] for p in prompts]
    sampling = SamplingParams(max_new_tokens=22)
    plain = make_engine(params, cfg, block=4).generate(prompts, sampling)
    spec = make_engine(params, cfg, block=4).generate(
        prompts, sampling, speculation=SpeculationConfig(k=4)
    )
    assert plain == spec


def test_greedy_parity_with_draft_model_drafter(plain_engine, spec_engine, decoder_params):
    """Exactness must hold for ANY drafter — here a differently-
    initialized (i.e. wrong) draft model: only throughput may differ."""
    draft_params = init_decoder_params(jax.random.key(99), CFG)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9], [10, 11, 12]]
    sampling = SamplingParams(max_new_tokens=15)
    plain = plain_engine.generate(prompts, sampling)
    sched = ContinuousBatchingScheduler(spec_engine, draft_params=draft_params)
    handles = [
        sched.submit(p, sampling, speculation=SpeculationConfig(k=3, method="draft_model"))
        for p in prompts
    ]
    while any(not h.done() for h in handles):
        if not sched.step():
            break
    assert [h.result(timeout=0) for h in handles] == plain


def test_draft_model_method_requires_params(spec_engine):
    sched = ContinuousBatchingScheduler(spec_engine)  # no draft_params
    with pytest.raises(ValueError):
        sched.submit([1, 2], SamplingParams(), speculation=SpeculationConfig(method="draft_model"))


def test_verify_jit_compiles_exactly_once(decoder_params):
    """Adaptive k, per-request k, batch recomposition, and k clamping
    all ride ONE verify program — the speculative analog of the
    steady-state-decode-never-recompiles contract."""
    engine = make_engine(decoder_params)
    prompts = [[1, 2, 3, 1, 2, 3], [5] * 10, [9, 8, 7], [4, 5] * 6]
    for k in (1, 2, 4, 64):  # 64 clamps to the engine window
        engine.generate(
            prompts, SamplingParams(max_new_tokens=9),
            speculation=SpeculationConfig(k=k, adaptive=(k % 2 == 0)),
        )
    assert engine.trace_counts.get("verify") == 1
    assert engine.recompiles() == {}


# ---------------------------------------------------------------------------
# rejection sampling: distribution preservation (statistical)
# ---------------------------------------------------------------------------


def test_speculative_accept_preserves_target_distribution():
    """The token emitted at a drafted position is distributed EXACTLY as
    the target distribution, whether the draft is likely or unlikely."""
    v, n = 8, 4000
    rs = np.random.RandomState(0)
    logits_row = jnp.asarray(rs.randn(v) * 1.5, jnp.float32)
    p_target = np.asarray(jax.nn.softmax(logits_row))
    keys = jax.random.split(jax.random.key(42), n)
    for draft_tok in (int(np.argmax(p_target)), int(np.argmin(p_target))):
        logits = jnp.tile(logits_row[None, None, :], (n, 2, 1))
        draft = jnp.full((n, 1), draft_tok, jnp.int32)
        out, n_em = speculative_accept(
            logits,
            draft,
            jnp.ones((n,), jnp.int32),
            jnp.ones((n,), jnp.float32),
            jnp.zeros((n,), jnp.int32),
            jnp.stack([keys, jax.random.split(jax.random.key(7), n)], axis=1),
        )
        first = np.asarray(out[:, 0])
        emp = np.bincount(first, minlength=v) / n
        assert np.abs(emp - p_target).sum() < 0.08, (
            f"draft={draft_tok}: L1(emp, target) = {np.abs(emp - p_target).sum():.3f}"
        )
        assert np.all(np.asarray(n_em) >= 1)


def test_rejection_sample_soft_proposal_preserves_distribution():
    """The general min(1, p/q) rule with a SOFT (non-point-mass)
    proposal still yields the target marginal."""
    v, n = 6, 5000
    rs = np.random.RandomState(1)
    p = jnp.asarray(jax.nn.softmax(jnp.asarray(rs.randn(v), jnp.float32)))
    q = jnp.asarray(jax.nn.softmax(jnp.asarray(rs.randn(v) * 2.0, jnp.float32)))
    keys = jax.random.split(jax.random.key(3), n)
    drafts = jax.vmap(lambda k: jax.random.categorical(k, jnp.log(q)))(keys)
    toks, _ = jax.vmap(lambda d, k: rejection_sample(p, q, d, k))(
        drafts, jax.random.split(jax.random.key(4), n)
    )
    emp = np.bincount(np.asarray(toks), minlength=v) / n
    assert np.abs(emp - np.asarray(p)).sum() < 0.08


def test_temperature_stream_replay_deterministic(spec_engine):
    """Same seed + same scheduling -> same sampled stream (per-token-
    count keys): the replay property preemption-exactness builds on."""
    prompts = [[1, 2, 1, 2, 1, 2, 1], [6, 7, 8, 9]]
    sampling = SamplingParams(max_new_tokens=12, temperature=0.9, top_k=12, seed=21)
    spec = SpeculationConfig(k=3)
    a = spec_engine.generate(prompts, sampling, speculation=spec)
    b = spec_engine.generate(prompts, sampling, speculation=spec)
    assert a == b


# ---------------------------------------------------------------------------
# scheduler properties
# ---------------------------------------------------------------------------


def test_mid_window_eos_truncates_exactly(plain_engine, spec_engine):
    """EOS landing mid-window stops the stream exactly where the
    non-speculative engine stops it: nothing after EOS leaks out."""
    prompt = [1, 2, 3, 1, 2, 3]
    plain = plain_engine.generate([prompt], SamplingParams(max_new_tokens=20))[0]
    eos = plain[7]  # guaranteed to land mid-window for k=4
    ref = plain[: plain.index(eos) + 1]
    spec_out = spec_engine.generate(
        [prompt], SamplingParams(max_new_tokens=20, eos_id=eos),
        speculation=SpeculationConfig(k=4),
    )[0]
    assert spec_out == ref
    assert spec_out.count(eos) == 1 and spec_out[-1] == eos


def test_preempt_with_speculation_recomputes_exactly(spec_engine, decoder_params):
    """Cache pressure preempts a speculating request; its recomputed
    stream continues token-for-token (greedy)."""
    p1, p2 = [1, 2, 3, 4, 5, 6, 7], [9, 10, 11, 12, 13, 14, 15, 16]
    sampling = SamplingParams(max_new_tokens=16)
    spec = SpeculationConfig(k=3)
    want = spec_engine.generate([p1, p2], sampling, speculation=spec)
    # 5 usable blocks of 8: the two sequences need 3 each at full
    # length even WITHOUT speculation, so after the pressure cap drains
    # step_k to zero the scheduler must still preempt-by-recompute
    from flexflow_tpu.generation import CacheConfig
    cc = CacheConfig(
        num_layers=CFG.num_layers, num_heads=CFG.num_heads,
        head_dim=CFG.hidden_size // CFG.num_heads, num_blocks=6, block_size=BLOCK,
    )
    tight = GenerationEngine(
        init_decoder_params(jax.random.key(0), CFG), CFG, cache_config=cc,
        max_batch_slots=2, prompt_buckets=BUCKETS, max_spec_tokens=4,
    )
    sched = ContinuousBatchingScheduler(tight)
    handles = [sched.submit(p, sampling, speculation=spec) for p in (p1, p2)]
    while any(not h.done() for h in handles):
        if not sched.step():
            break
    got = [h.result(timeout=0) for h in handles]
    assert got == want
    assert sched.preemptions > 0, "cache was too roomy to exercise preemption"
    assert_blocks_conserved(tight)


def test_block_boundary_partial_acceptance_accounting(decoder_params):
    """Windows crossing block boundaries with partial acceptance and a
    temperature mix must leave the allocator exactly drained: no leaks,
    no double frees, trailing garbage blocks trimmed."""
    engine = make_engine(decoder_params, block=4)
    sched = ContinuousBatchingScheduler(engine)
    rs = np.random.RandomState(2)
    handles = []
    for i in range(7):
        prompt = rs.randint(0, CFG.vocab_size, rs.randint(3, 18)).tolist()
        sampling = SamplingParams(
            max_new_tokens=int(rs.randint(1, 18)),
            temperature=float(rs.choice([0.0, 0.9])),
            seed=i,
        )
        spec = SpeculationConfig(k=int(rs.randint(1, 5))) if i % 3 else None
        handles.append(sched.submit(prompt, sampling, speculation=spec))
    while any(not h.done() for h in handles):
        if not sched.step():
            break
    for h in handles:
        out = h.result(timeout=0)
        assert 1 <= len(out) <= 18
    assert_blocks_conserved(engine)
    ss = sched.spec_stats
    assert ss.accepted <= ss.proposed
    assert ss.emitted >= ss.accepted


def test_adaptive_k_shrinks_and_regrows():
    from flexflow_tpu.generation.scheduler import Request

    cfg = SpeculationConfig(k=4, low_acceptance=0.3, high_acceptance=0.8, ema_alpha=1.0)
    req = Request([1], SamplingParams(), speculation=cfg, drafter=NgramDrafter())
    assert req.spec_k == 4
    req.update_speculation(proposed=4, accepted=0)  # ema 0.0 -> shrink
    assert req.spec_k == 3
    req.update_speculation(proposed=3, accepted=0)
    req.update_speculation(proposed=2, accepted=0)
    req.update_speculation(proposed=1, accepted=0)
    assert req.spec_k == 1  # floor: never below 1
    for _ in range(4):
        req.update_speculation(proposed=1, accepted=1)  # ema 1.0 -> grow
    assert req.spec_k == 4  # ceiling: back at config.k
    assert req.spec_proposed == 14 and req.spec_accepted == 4


# ---------------------------------------------------------------------------
# chaos: the generation.verify fault site
# ---------------------------------------------------------------------------


def test_chaos_verify_transient_retries_then_exact(spec_engine):
    """A transient fault on the first verify step is retried and the
    stream still comes out exact."""
    engine = spec_engine
    want = engine.generate(
        [[1, 2, 3, 1, 2, 3]], SamplingParams(max_new_tokens=10),
        speculation=SpeculationConfig(k=3),
    )
    sched = ContinuousBatchingScheduler(
        engine, retry=RetryPolicy(max_attempts=3, base_delay_s=0.0)
    )
    plan = FaultPlan(seed=0)
    plan.on("generation.verify", mode="error", error=TransientDeviceError("blip"), nth=(0,))
    with plan.active():
        h = sched.submit(
            [1, 2, 3, 1, 2, 3], SamplingParams(max_new_tokens=10),
            speculation=SpeculationConfig(k=3),
        )
        while not h.done():
            if not sched.step():
                break
    assert plan.fired("generation.verify") == 1
    assert [h.result(timeout=0)] == want


def test_chaos_verify_poison_fails_batch(spec_engine):
    engine = spec_engine
    sched = ContinuousBatchingScheduler(engine)
    plan = FaultPlan(seed=0)
    plan.on("generation.verify", mode="error", error=FaultInjected("poisoned"), every=1)
    with plan.active():
        h = sched.submit(
            [1, 2, 3, 4], SamplingParams(max_new_tokens=8),
            speculation=SpeculationConfig(k=2),
        )
        while not h.done():
            if not sched.step():
                break
    with pytest.raises(FaultInjected):
        h.result(timeout=0)
    assert_blocks_conserved(engine)


# ---------------------------------------------------------------------------
# serving surface: stats + HTTP speculation block
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def spec_server(decoder_params):
    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    eng = make_engine(decoder_params, slots=2)
    srv = InferenceServer(port=0)
    srv.register_generation(GenerationModel(eng, name="lm"))
    srv.start()
    yield srv
    srv.stop()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), headers={"Content-Type": "application/json"}
    )
    return urllib.request.urlopen(req, timeout=60)


def test_http_generate_with_speculation_block(spec_server, plain_engine):
    base = f"http://127.0.0.1:{spec_server.port}"
    prompt = [1, 2, 3, 1, 2, 3, 1, 2]
    # greedy is scheduler-invariant: the shared engine's output IS the
    # HTTP reference whatever the server's slot count is
    want = plain_engine.generate([prompt], SamplingParams(max_new_tokens=12))[0]
    resp = json.load(
        _post(
            f"{base}/v2/models/lm/generate",
            {
                "prompt": prompt,
                "max_new_tokens": 12,
                "speculation": {"k": 4, "method": "ngram"},
            },
        )
    )
    assert resp["tokens"] == want  # exactness through the HTTP path
    stats = json.load(urllib.request.urlopen(f"{base}/v2/stats", timeout=30))
    lm = stats["generation"]["lm"]
    assert lm["spec_windows"] >= 1
    assert lm["spec_tokens_proposed"] >= 1
    assert 0.0 <= lm["spec_acceptance_rate"] <= 1.0
    assert lm["spec_mean_accepted_len"] >= 0.0
    assert lm["spec_tokens_accepted"] <= lm["spec_tokens_proposed"]


def test_http_generate_speculation_disabled_block(spec_server, plain_engine):
    """enabled: false opts out — still exact, no new speculation
    windows beyond the previous test's."""
    base = f"http://127.0.0.1:{spec_server.port}"
    before = json.load(urllib.request.urlopen(f"{base}/v2/stats", timeout=30))
    resp = json.load(
        _post(
            f"{base}/v2/models/lm/generate",
            {"prompt": [5, 6, 7], "max_new_tokens": 6, "speculation": {"enabled": False}},
        )
    )
    assert resp["tokens"] == plain_engine.generate(
        [[5, 6, 7]], SamplingParams(max_new_tokens=6)
    )[0]
    after = json.load(urllib.request.urlopen(f"{base}/v2/stats", timeout=30))
    assert (
        after["generation"]["lm"]["spec_windows"]
        == before["generation"]["lm"]["spec_windows"]
    )


def test_speculation_metadata(spec_server):
    base = f"http://127.0.0.1:{spec_server.port}"
    meta = json.load(urllib.request.urlopen(f"{base}/v2/models/lm", timeout=30))
    assert meta["max_spec_tokens"] == 4
