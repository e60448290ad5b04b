"""Constrained-decoding subsystem tests (ISSUE 18).

Acceptance criteria covered:
  * grammar pipeline units: regex -> char DFA, JSON-Schema -> regex,
    token DFA liveness pruning, MaskState advance/dead-end semantics,
    draft filtering, journal replay via state_after, compile-once cache
  * exactness matrix: constrained streams byte-identical within every
    (sampling, speculation) configuration across overlap on/off and
    repeat trials; greedy additionally across speculation on/off and
    prefix cache on/off; every stream parses + validates against its
    schema
  * crash replay: a decode-step fault mid-constrained-stream journal-
    replays byte-exactly and the replayed stream stays schema-valid
  * mixed batches: an unconstrained companion stream is byte-identical
    to its solo run; a mask fault injected into the constrained slot
    quarantines that slot alone with a typed step="mask" error
  * zero new steady-state programs: a constrained batch adds no jit
    traces beyond the warmed engine's
  * serving surface: HTTP response_format (JSON + SSE) end-to-end,
    400 on a malformed grammar, constrained metadata + stats blocks
  * SIM_TUNE drift guard: the checked-in threshold sweep's winner and
    the OverloadConfig serving defaults cannot disagree
"""
import json
import os
import urllib.error
import urllib.request

import jax
import pytest

from flexflow_tpu.generation import (
    ContinuousBatchingScheduler,
    GenerationEngine,
    PoisonedRequestError,
    RecoveryPolicy,
    SamplingParams,
    SpeculationConfig,
    init_decoder_params,
)
from flexflow_tpu.generation.constrained import (
    GrammarCache,
    GrammarError,
    MaskAdvanceError,
    MaskState,
    TokenDFA,
    compile_regex,
    compile_response_format,
    decode_text,
    default_vocabulary,
    grammar_alphabet,
    schema_to_regex,
    validate_json,
)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime.faults import FaultPlan
from flexflow_tpu.serving.stats import ConstrainedStats

from conftest import assert_blocks_conserved  # noqa: E402

pytestmark = pytest.mark.constrained

CFG = TransformerConfig(
    num_layers=2, hidden_size=32, num_heads=4, ff_size=64,
    seq_length=64, vocab_size=50, causal=True,
)
BUCKETS = (8, 16, 32, 64)
BLOCK = 8
VOCAB = default_vocabulary(50)
SCHEMA = {
    "type": "object",
    "properties": {"ok": {"type": "boolean"}, "n": {"type": "integer"}},
}
SPEC = {"type": "json_schema", "json_schema": SCHEMA}
DFA = compile_response_format(SPEC, VOCAB)
# a unit-test EOS id the object grammar never uses as a character
# ('_'), so allowing it at accepting states shadows no grammar edge
EOS = VOCAB.index("_")
NO_SLEEP = RecoveryPolicy(sleep=lambda _s: None)


@pytest.fixture(scope="module")
def decoder_params():
    return init_decoder_params(jax.random.key(0), CFG)


def make_engine(params, *, prefix_cache=True, slots=3):
    return GenerationEngine(
        params, CFG, max_batch_slots=slots, block_size=BLOCK,
        prompt_buckets=BUCKETS, max_spec_tokens=4,
        prefix_cache=prefix_cache,
    )


@pytest.fixture(scope="module")
def engine(decoder_params):
    """Shared warmed engine: jit traces amortize across the module."""
    return make_engine(decoder_params)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    assert faults.active_plan() is None, "a test leaked an installed FaultPlan"


# ---------------------------------------------------------------------------
# grammar pipeline units
# ---------------------------------------------------------------------------


def test_char_dfa_accepts_and_rejects():
    dfa = compile_regex("(yes|no|maybe)", grammar_alphabet(VOCAB))
    for word, want in (("yes", True), ("no", True), ("maybe", True),
                       ("ye", False), ("nope", False), ("", False)):
        state = dfa.start
        dead = False
        for ch in word:
            state = dfa.step(state, ch)
            if state is None:
                dead = True
                break
        if dead:
            assert want is False, word
        else:
            assert (state in dfa.accepting) == want, word


def test_schema_to_regex_round_trip():
    """Strings the schema regex accepts must validate as JSON against
    the schema — the lowering may narrow but never widen."""
    dfa = compile_regex(schema_to_regex(SCHEMA), grammar_alphabet(VOCAB))
    for text in ('{"ok":true,"n":7}', '{"ok":false,"n":-12}'):
        state = dfa.start
        for ch in text:
            state = dfa.step(state, ch)
            assert state is not None, (text, ch)
        assert state in dfa.accepting
        assert validate_json(text, SCHEMA) == []
    assert validate_json('{"ok":1}', SCHEMA)
    assert validate_json("not json", SCHEMA)


def test_malformed_response_format_is_typed():
    for bad in (
        42,
        {"type": "csv"},
        {"type": "json_schema"},
        {"type": "json_schema", "json_schema": []},
        {"type": "regex", "pattern": ""},
    ):
        with pytest.raises(GrammarError):
            compile_response_format(bad, VOCAB)


def test_token_dfa_mask_row_bans_illegal_tokens():
    open_brace = VOCAB.index("{")
    digit = VOCAB.index("7")
    row = DFA.mask_row(DFA.start, None)
    assert row[open_brace] == 0.0          # '{' starts the object
    assert row[digit] < -1e29              # a bare digit cannot
    # eos is only legal at an accepting state; start is not accepting
    assert DFA.mask_row(DFA.start, EOS)[EOS] < -1e29


def test_token_dfa_liveness_pruning():
    """A char edge whose continuation no vocabulary token can spell is
    pruned from the TOKEN automaton: 'Z' appears in no token, so the
    optional 'aZ' branch is a trap and 'a' must be banned up front even
    though the character DFA happily steps on it."""
    dfa = compile_response_format(
        {"type": "regex", "pattern": "(aZ)?b"}, VOCAB)
    a, b = VOCAB.index("a"), VOCAB.index("b")
    assert dfa.char_dfa.step(dfa.char_dfa.start, "a") is not None
    row0 = dfa.mask_row(dfa.start, None)
    assert row0[b] == 0.0
    assert row0[a] < -1e29


def test_mask_state_walk_and_completion():
    ms = MaskState(DFA)
    text = '{"ok":true,"n":3}'
    for ch in text:
        ms.advance(VOCAB.index(ch), EOS)
    # accepting: eos is now legal and finishes the stream
    assert ms.mask_row(EOS)[EOS] == 0.0
    ms.advance(EOS, EOS)
    assert ms.done
    with pytest.raises(MaskAdvanceError):
        ms.advance(VOCAB.index("a"), EOS)
    # a refused token is typed without corrupting a fresh cursor
    ms2 = MaskState(DFA)
    with pytest.raises(MaskAdvanceError):
        ms2.advance(VOCAB.index("9"), EOS)
    # eos at a NON-accepting state is refused too
    ms3 = MaskState(DFA)
    ms3.advance(VOCAB.index("{"), EOS)
    with pytest.raises(MaskAdvanceError):
        ms3.advance(EOS, EOS)


def test_filter_draft_and_states_along_match_advance():
    ms = MaskState(DFA)
    legal = [VOCAB.index(c) for c in '{"ok":']
    draft = legal + [VOCAB.index("z")]  # 'z' is illegal after '"ok":'
    kept = ms.filter_draft(draft, EOS)
    assert kept == legal
    states = ms.states_along(kept, EOS)
    assert len(states) == len(kept)
    # states_along must agree with actually advancing
    for tok, want in zip(kept, states):
        ms.advance(tok, EOS)
        assert ms.state == want


def test_state_after_replays_journal():
    ms = MaskState(DFA)
    toks = [VOCAB.index(c) for c in '{"ok":true']
    for t in toks:
        ms.advance(t, EOS)
    replayed = DFA.state_after(toks, EOS)
    assert replayed.state == ms.state
    assert replayed.n_advanced == len(toks)


def test_grammar_cache_compiles_once():
    stats = ConstrainedStats()
    cache = GrammarCache(VOCAB, stats=stats)
    g1 = cache.get(SPEC)
    g2 = cache.get(SPEC)
    assert g1 is g2
    assert isinstance(g1, TokenDFA)
    assert len(cache) == 1
    assert stats.grammar_cache_misses == 1
    assert stats.grammar_cache_hits == 1
    assert stats.grammar_compile_seconds > 0.0


# ---------------------------------------------------------------------------
# exactness matrix
# ---------------------------------------------------------------------------


def _run(engine, sampling, *, overlap, spec_k, recovery=None):
    """One constrained stream + an unconstrained companion on a fresh
    scheduler over ``engine``. Returns (constrained tokens, companion
    tokens, scheduler)."""
    kw = {"overlap": overlap}
    if recovery is not None:
        kw["recovery"] = recovery
    sched = ContinuousBatchingScheduler(engine, **kw)
    skw = {}
    if spec_k:
        skw["speculation"] = SpeculationConfig(k=spec_k)
    h = sched.submit([1, 2, 3], sampling, grammar=DFA,
                     response_format=SPEC, **skw)
    h2 = sched.submit([4, 5], sampling)
    for _ in range(800):
        if h.done() and h2.done():
            break
        if not sched.step():
            break
    return h.result(timeout=0), h2.result(timeout=0), sched


def test_greedy_exact_across_overlap_speculation_prefix(decoder_params, engine):
    """Greedy constrained streams are byte-identical across overlap
    on/off, speculation on/off, AND prefix cache on/off — and always
    schema-valid."""
    sampling = SamplingParams(max_new_tokens=48)
    base = None
    for eng in (engine, make_engine(decoder_params, prefix_cache=False)):
        for overlap in (False, True):
            for k in (0, 3):
                toks, companion, _ = _run(eng, sampling, overlap=overlap,
                                          spec_k=k)
                text = decode_text(VOCAB, toks, sampling.eos_id)
                assert validate_json(text, SCHEMA) == [], text
                if base is None:
                    base = (toks, companion)
                assert (toks, companion) == base, (overlap, k)


def test_seeded_temperature_exact_within_config(engine):
    """Seeded-temperature constrained streams are byte-identical
    within each speculation setting, across overlap on/off and repeat
    trials, and always schema-valid. (Across speculation settings the
    repo promises distribution preservation, not byte equality — a
    different window layout realizes a different, equally-distributed
    key stream.)"""
    sampling = SamplingParams(max_new_tokens=48, temperature=0.9, seed=7)
    per_k = {}
    for _trial in range(2):
        for overlap in (False, True):
            for k in (0, 3):
                toks, _, _ = _run(engine, sampling, overlap=overlap,
                                  spec_k=k)
                text = decode_text(VOCAB, toks, sampling.eos_id)
                assert validate_json(text, SCHEMA) == [], text
                ref = per_k.setdefault(k, toks)
                assert toks == ref, (overlap, k)


def test_constrained_adds_no_steady_state_programs(engine):
    """After the exactness matrix warmed every path, further
    constrained runs must hit only cached jit traces — the mask is a
    staged operand on the existing programs, not a new program — and
    under plain load a mask is applied and no stream dead-ends."""
    before = dict(engine.trace_counts)
    scheds = [
        _run(engine, SamplingParams(max_new_tokens=24), overlap=False, spec_k=3)[2],
        _run(engine, SamplingParams(max_new_tokens=24), overlap=True, spec_k=0)[2],
    ]
    grown = {k: c - before.get(k, 0) for k, c in engine.trace_counts.items()
             if c - before.get(k, 0) > 0}
    assert grown == {}, f"constrained batches retraced: {grown}"
    # fault-free: every step of the constrained slot was masked, none dead-ended
    for sched in scheds:
        cs = sched.constrained_stats
        assert cs.masked_steps > 0 and cs.dead_end_failures == 0
    assert_blocks_conserved(engine)


def test_crash_replay_byte_exact(decoder_params):
    """A double decode-step fault mid-constrained-stream rides the
    supervisor's retry -> restart ladder into journal replay: the
    automaton is rebuilt by re-advancing over the journaled tokens and
    the stream comes out byte-exact and schema-valid. Own engine: the
    restart resets engine state the other tests share."""
    eng = make_engine(decoder_params)
    sampling = SamplingParams(max_new_tokens=40)
    ref, ref2, _ = _run(eng, sampling, overlap=False, spec_k=0,
                        recovery=NO_SLEEP)
    plan = FaultPlan(seed=0)
    plan.on(faults.GENERATION_DECODE_STEP, mode="error",
            error=RuntimeError("injected device crash"), nth=(2, 3))
    with plan.active():
        got, got2, sched = _run(eng, sampling, overlap=False, spec_k=0,
                                recovery=NO_SLEEP)
    assert plan.fired(faults.GENERATION_DECODE_STEP) == 2
    assert (got, got2) == (ref, ref2)
    text = decode_text(VOCAB, got, sampling.eos_id)
    assert validate_json(text, SCHEMA) == [], text
    assert sched.recovery_stats.recoveries == 1
    assert sched.recovery_stats.replayed_tokens > 0
    assert_blocks_conserved(eng)


# ---------------------------------------------------------------------------
# mixed batches + typed failure isolation
# ---------------------------------------------------------------------------


def test_unconstrained_companion_unaffected(engine):
    """An unconstrained stream sharing a batch with a constrained one
    is byte-identical to its solo run."""
    sampling = SamplingParams(max_new_tokens=24)
    sched = ContinuousBatchingScheduler(engine, overlap=False)
    solo = sched.submit([4, 5], sampling)
    for _ in range(400):
        if solo.done():
            break
        if not sched.step():
            break
    _, companion, _ = _run(engine, sampling, overlap=False, spec_k=0)
    assert companion == solo.result(timeout=0)


def test_mask_advance_fault_quarantines_one_slot(engine):
    """A mask-advance fault fails ONLY the constrained request, typed
    step='mask'; the unconstrained companion stream survives
    byte-exactly and no blocks leak."""
    sampling = SamplingParams(max_new_tokens=24)
    _, ref_companion, _ = _run(engine, sampling, overlap=False, spec_k=0)
    plan = FaultPlan(seed=0)
    plan.on(faults.GENERATION_MASK_ADVANCE, mode="error",
            error=RuntimeError("injected advance fault"), nth=(5,))
    with plan.active():
        sched = ContinuousBatchingScheduler(engine, overlap=False,
                                            recovery=NO_SLEEP)
        h = sched.submit([1, 2, 3], sampling, grammar=DFA,
                         response_format=SPEC)
        h2 = sched.submit([4, 5], sampling)
        for _ in range(400):
            if h.done() and h2.done():
                break
            if not sched.step():
                break
    assert plan.fired(faults.GENERATION_MASK_ADVANCE) == 1
    with pytest.raises(PoisonedRequestError) as exc:
        h.result(timeout=0)
    assert exc.value.step == "mask"
    assert h2.result(timeout=0) == ref_companion
    assert sched.constrained_stats.dead_end_failures == 1
    assert sched.recovery_stats.quarantined == 1
    assert_blocks_conserved(engine)


def test_mask_build_fault_is_pre_queue_and_clean():
    """A grammar-compile fault surfaces to the submitting caller before
    anything is queued; the retry compiles clean from the same cache."""
    cache = GrammarCache(VOCAB)
    plan = FaultPlan(seed=0)
    plan.on(faults.GENERATION_MASK_BUILD, mode="error",
            error=RuntimeError("injected compile failure"), nth=(0,))
    with plan.active():
        with pytest.raises(RuntimeError):
            cache.get(SPEC)
        assert len(cache) == 0
        assert cache.get(SPEC) is not None  # retry compiles clean
    assert plan.fired(faults.GENERATION_MASK_BUILD) == 1


def test_grammar_vocab_mismatch_rejected(engine):
    sched = ContinuousBatchingScheduler(engine)
    # 49 tokens still spell the grammar (compile succeeds) but the
    # size disagrees with the engine's vocab of 50
    wrong = compile_response_format(SPEC, default_vocabulary(49))
    with pytest.raises(ValueError):
        sched.submit([1, 2], SamplingParams(max_new_tokens=4), grammar=wrong)


# ---------------------------------------------------------------------------
# serving surface: HTTP JSON + SSE + metadata/stats
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def server(decoder_params):
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
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=60)


def test_http_response_format_json(server):
    base = f"http://127.0.0.1:{server.port}"
    resp = json.load(_post(
        f"{base}/v2/models/lm/generate",
        {"prompt": [1, 2, 3], "max_new_tokens": 48,
         "response_format": SPEC},
    ))
    text = decode_text(VOCAB, resp["tokens"], None)
    assert validate_json(text, SCHEMA) == [], text
    stats = json.load(urllib.request.urlopen(f"{base}/v2/stats", timeout=30))
    lm = stats["generation"]["lm"]
    assert lm["constrained_masked_steps_total"] >= 1
    assert lm["constrained_grammar_cache_misses_total"] >= 1
    meta = json.load(
        urllib.request.urlopen(f"{base}/v2/models/lm", timeout=30))
    con = meta["constrained"]
    assert con["grammar_cache_entries"] >= 1
    assert con["vocabulary_tokens"] == 50
    assert "json_schema" in con["formats"]


def test_http_response_format_sse(server):
    base = f"http://127.0.0.1:{server.port}"
    r = _post(
        f"{base}/v2/models/lm/generate",
        {"prompt": [1, 2, 3], "max_new_tokens": 48, "stream": True,
         "response_format": SPEC},
    )
    assert r.headers["Content-Type"] == "text/event-stream"
    # each SSE chunk is an `id: N` line (durable resume cursor) + a data line
    events = [json.loads(ln.split("data: ", 1)[1])
              for ln in r.read().decode().strip().split("\n\n")]
    assert events[-1]["done"] is True
    toks = events[-1]["tokens"]
    assert [e["token"] for e in events[:-1]] == toks
    text = decode_text(VOCAB, toks, None)
    assert validate_json(text, SCHEMA) == [], text


def test_http_malformed_grammar_is_400(server):
    base = f"http://127.0.0.1:{server.port}"
    for bad in ({"type": "csv"}, {"type": "regex", "pattern": ""}, 7):
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(
                f"{base}/v2/models/lm/generate",
                {"prompt": [1, 2], "max_new_tokens": 4,
                 "response_format": bad},
            )
        assert exc.value.code == 400


# ---------------------------------------------------------------------------
# SIM_TUNE drift guard
# ---------------------------------------------------------------------------


def test_sim_tune_defaults_match_checked_in_winner():
    """The OverloadConfig serving defaults carry the simfleet tune
    sweep's winner (SIM_TUNE.json). Re-run `python tools/simfleet.py
    tune` and check in the result before moving either side."""
    from flexflow_tpu.serving.overload import OverloadConfig

    path = os.path.join(os.path.dirname(__file__), "..", "SIM_TUNE.json")
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"] == "flexflow-sim-tune-v1"
    assert doc["defaults_match_winner"] is True
    cfg = OverloadConfig()
    winner = doc["winner"]
    assert winner["up_threshold"] == cfg.up_threshold
    assert winner["down_threshold"] == cfg.down_threshold
    assert winner["min_queue_frac"] == cfg.min_queue_frac
    # the recorded defaults must be the CURRENT defaults too — a
    # defaults edit without a re-run shows up here
    assert doc["serving_defaults"] == {
        "up_threshold": cfg.up_threshold,
        "down_threshold": cfg.down_threshold,
        "min_queue_frac": cfg.min_queue_frac,
    }
