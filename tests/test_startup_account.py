"""The start-up account (ISSUE 50): ``obs.steptrace.GLOBAL_STARTUP`` and the
compile events ``obs.capacity`` takes from JAX.

What is held here:
  * the identities: a parent span's ``self_s`` is its seconds less its
    children's; a program's ``trace_s + lower_s + compile_s +
    cache_load_s + run_s`` is the lumped ``compile_s`` of its registry;
    with JAX's cache thresholds at zero ``hits + misses = requests``
  * JAX's events go to the program whose ``note_trace`` ran on the SAME
    thread (two programs traced at once on two threads keep their own),
    the OUTERMOST program of a trace keeps them, a compile inside a trace
    is compile seconds, and a retrace's record carries blame AND split
  * the span list is bounded and the totals are not
  * a tiny engine run twice in subprocesses over one cache directory:
    the first misses and compiles, the second loads everything
  * where an operator reads it: ``/v2/stats`` ``startup``,
    ``/v2/debug/programs``, ``FFModel.compile``'s line, the executor's
    train step
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import ActiMode, FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.obs.capacity import GLOBAL_PROGRAMS, ProgramRegistry
from flexflow_tpu.obs.steptrace import GLOBAL_STARTUP, PROGRAM_PARTS, StartupAccount

pytestmark = pytest.mark.observability

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _room_in_the_process_s_account(monkeypatch):
    """The account keeps a process's FIRST spans and programs (it is a
    start-up account); a test worker that has run a thousand other tests
    has filled both lists, and these tests read what they add."""
    monkeypatch.setattr(GLOBAL_STARTUP, "max_spans", 10**9)
    monkeypatch.setattr(GLOBAL_STARTUP, "max_programs", 10**9)


def _fresh(n):
    """An argument of a shape no other test of this process compiles."""
    return jnp.ones((n, 3), jnp.float32)


def _call(reg, name, jitted, *args):
    """One host call of ``jitted``, its wall stamped as the engine does."""
    t0 = time.perf_counter()
    jax.block_until_ready(jitted(*args))
    reg.set_compile_time(name, time.perf_counter() - t0)


def _program(reg, name, sleep_s=0.0, inner=None):
    def body(x):
        reg.note_trace(name, {"x": x})
        if sleep_s:
            time.sleep(sleep_s)
        return (inner(x) if inner is not None else x) * 2.0 + 1.0
    return jax.jit(body)


# ------------------------------------------------------------- the spans

def test_a_parent_s_self_time_is_its_seconds_less_its_children_s():
    acct = StartupAccount(origin=time.perf_counter())
    with acct.span("search"):
        with acct.span("search.calibrate"):
            time.sleep(0.02)
            acct.annotate(calibration="committed_table")
        with acct.span("search.unity", budget=5):
            time.sleep(0.03)
            acct.annotate(graphs_costed=7)
        time.sleep(0.01)
    with acct.span("mesh"):
        pass
    snap = acct.snapshot()
    ph = snap["phases"]
    assert set(ph) == {"search", "search.calibrate", "search.unity", "mesh"}
    kids = ph["search.calibrate"]["total_s"] + ph["search.unity"]["total_s"]
    assert ph["search"]["self_s"] == pytest.approx(ph["search"]["total_s"] - kids, abs=1e-12)
    assert ph["search"]["self_s"] >= 0.01 and ph["search.unity"]["self_s"] == ph["search.unity"]["total_s"]
    by_name = {s[0]: s for s in snap["spans"]}
    assert by_name["search.calibrate"][1] == by_name["search.unity"][1] == "search" and by_name["search"][1] is None
    assert by_name["search.calibrate"][4] == {"calibration": "committed_table"}
    assert by_name["search.unity"][4] == {"budget": 5, "graphs_costed": 7}
    # children lie inside their parent, offsets count from the origin
    s0, s1 = by_name["search"][2], by_name["search"][2] + by_name["search"][3]
    assert all(s0 <= by_name[k][2] and by_name[k][2] + by_name[k][3] <= s1 for k in ("search.calibrate", "search.unity"))
    assert 0.0 <= s0 < 1.0
    # the union of the top-level spans: a child adds nothing to it
    assert snap["spanned_s"] == pytest.approx(ph["search"]["total_s"] + ph["mesh"]["total_s"], abs=1e-9)


def test_the_span_list_is_bounded_and_the_totals_are_not():
    acct = StartupAccount(origin=time.perf_counter(), max_spans=4)
    for _ in range(10):
        with acct.span("executor"):
            with acct.span("search.calibrate"):
                pass
    snap = acct.snapshot()
    assert len(snap["spans"]) == 4
    assert snap["phases"]["executor"]["count"] == snap["phases"]["search.calibrate"]["count"] == 10
    assert snap["phases"]["executor"]["self_s"] == pytest.approx(
        snap["phases"]["executor"]["total_s"] - snap["phases"]["search.calibrate"]["total_s"], abs=1e-12)


def test_a_snapshot_cut_at_an_offset_keeps_what_had_ended_by_then():
    t = time.perf_counter()
    acct = StartupAccount(origin=t)
    acct.note_span("import", t, t + 3.0)
    acct.note_span("engine_build", t + 4.0, t + 5.0)
    acct.note_span("param_init", t + 9.0, t + 11.0)  # still open at 10
    rec = {"name": "late", "at_s": 12.0, "end_s": 13.0, **{k: 0.25 for k in PROGRAM_PARTS}, "cache_hit": None, "lump_s": None, "run_s": None}
    acct.add_program(dict(rec, name="early", at_s=6.0, end_s=7.0))
    acct.add_program(rec)
    cut = acct.snapshot(until_s=10.0)
    assert set(cut["phases"]) == {"import", "engine_build"} and set(cut["programs"]) == {"early"}
    assert cut["spanned_s"] == pytest.approx(3.0 + 1.0 + 1.0)
    assert set(acct.snapshot()["phases"]) == {"import", "engine_build", "param_init"}
    assert acct.snapshot()["spanned_s"] == pytest.approx(3.0 + 1.0 + 1.0 + 2.0 + 1.0)


def test_the_process_s_account_counts_from_the_process_s_start_and_holds_the_import():
    snap = GLOBAL_STARTUP.snapshot()
    assert snap["origin"] in ("process_start", "import")
    imp = [s for s in snap["spans"] if s[0] == "import"]
    assert len(imp) == 1 and imp[0][2] == 0.0 and 0.0 < imp[0][3] <= snap["now_s"]
    GLOBAL_STARTUP.mark_import()  # once a process
    assert GLOBAL_STARTUP.snapshot()["phases"]["import"]["count"] == 1


def test_the_decorator_spans_the_whole_call_and_keeps_the_signature():
    import inspect

    from flexflow_tpu.generation import GenerationEngine

    assert "max_batch_slots" in inspect.signature(GenerationEngine.__init__).parameters
    acct = StartupAccount(origin=time.perf_counter())

    @acct.spanned("engine_build")
    def build(a, b=2):
        return a + b

    assert build(1, b=5) == 6 and acct.snapshot()["phases"]["engine_build"]["count"] == 1
    with pytest.raises(ZeroDivisionError):
        with acct.span("mesh"):
            with acct.span("executor"):
                1 / 0
    assert acct.snapshot()["phases"]["mesh"]["count"] == 1
    with acct.span("param_init"):  # the stack unwound: this one has no parent
        pass
    assert [s[1] for s in acct.snapshot()["spans"] if s[0] == "param_init"] == [None]


# ----------------------------------------------------- programs, by JAX's events

def test_a_program_s_parts_and_its_run_add_up_to_the_lump():
    reg = ProgramRegistry()
    f = _program(reg, "step")
    _call(reg, "step", f, _fresh(101))
    (p,) = reg.snapshot()
    assert p["traces"] == 1 and p["trace_s"] > 0 and p["lower_s"] > 0
    assert p["compile_s_backend"] + p["cache_load_s"] > 0
    parts = p["trace_s"] + p["lower_s"] + p["compile_s_backend"] + p["cache_load_s"]
    assert parts + p["run_s"] == pytest.approx(p["compile_s"], abs=1e-9) and p["run_s"] >= 0.0
    # the same record is the process's account's, under the program's name
    cycle = reg.entries["step"].cycle
    assert cycle["name"] == "step" and cycle["lump_s"] == p["compile_s"] and cycle["end_s"] >= cycle["at_s"] + parts
    # a warm call changes nothing
    before = dict(cycle)
    jax.block_until_ready(f(_fresh(101)))
    assert reg.entries["step"].traces == 1 and reg.entries["step"].cycle == before and "step" not in reg.unstamped


def test_two_threads_tracing_at_once_keep_their_own_events():
    regs = {"a": ProgramRegistry(), "b": ProgramRegistry()}
    gate = threading.Barrier(2)
    sleeps = {"a": 0.4, "b": 0.0}

    def work(k):
        def body(x):
            regs[k].note_trace("decode", {"x": x})
            gate.wait(timeout=30)  # both bodies are being traced now
            time.sleep(sleeps[k])
            return x * (3.0 if k == "a" else 5.0)
        _call(regs[k], "decode", jax.jit(body), _fresh(103 if k == "a" else 105))

    threads = [threading.Thread(target=work, args=(k,)) for k in regs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    a, b = regs["a"].snapshot()[0], regs["b"].snapshot()[0]
    assert a["trace_s"] >= 0.4 > b["trace_s"] > 0.0
    for p in (a, b):
        parts = p["trace_s"] + p["lower_s"] + p["compile_s_backend"] + p["cache_load_s"]
        assert parts + p["run_s"] == pytest.approx(p["compile_s"], abs=1e-9) and p["run_s"] >= 0.0


def test_the_outermost_program_of_a_trace_keeps_the_events():
    reg = ProgramRegistry()
    inner = _program(reg, "inner", sleep_s=0.2)
    _call(reg, "outer", _program(reg, "outer", inner=inner), _fresh(107))
    by = {p["name"]: p for p in reg.snapshot()}
    assert by["outer"]["trace_s"] >= 0.2  # the inner jit's trace lies inside the outer's
    assert by["inner"]["traces"] == 1 and by["inner"]["trace_s"] is None and by["inner"]["compile_s"] is None
    names = [p for p in GLOBAL_STARTUP.snapshot()["programs"] if p in ("inner", "outer")]
    assert names == ["outer"]
    parts = sum(by["outer"][k] for k in ("trace_s", "lower_s", "compile_s_backend", "cache_load_s"))
    assert parts + by["outer"]["run_s"] == pytest.approx(by["outer"]["compile_s"], abs=1e-9)


def test_a_compile_inside_a_trace_is_compile_seconds_of_the_outer_program():
    reg = ProgramRegistry()
    eager = jax.jit(lambda v: jnp.cumsum(v * 1.5))
    seen = {}

    def body(x):
        reg.note_trace("prefill", {"x": x})
        t0 = time.perf_counter()
        with jax.ensure_compile_time_eval():  # concrete: compiles and runs, now
            seen["n"] = float(eager(np.arange(109, dtype=np.float32))[-1])
        seen["s"] = time.perf_counter() - t0
        return x + seen["n"]

    calls = lambda: sum(p["calls"] for p in GLOBAL_STARTUP.snapshot()["programs"].values())  # noqa: E731
    x = _fresh(109)  # (making it compiles a program of its own)
    before = calls()
    _call(reg, "prefill", jax.jit(body), x)
    (p,) = reg.snapshot()
    # the eager program's backend seconds are out of the trace's and in the compile's
    assert p["compile_s_backend"] + p["cache_load_s"] > 0 and p["trace_s"] > 0
    parts = p["trace_s"] + p["lower_s"] + p["compile_s_backend"] + p["cache_load_s"]
    assert parts + p["run_s"] == pytest.approx(p["compile_s"], abs=1e-9) and p["run_s"] >= -1e-9
    assert calls() == before + 1  # the eager program is no program of its own


def test_a_retrace_s_record_carries_blame_and_split():
    reg = ProgramRegistry()
    said = []
    reg.on_retrace = lambda name, blame: said.append(blame)
    f = _program(reg, "decode")
    _call(reg, "decode", f, _fresh(111))
    assert reg.recent_retraces() == []
    _call(reg, "decode", f, _fresh(113))
    (r,) = reg.recent_retraces()
    assert r["blame"] == "decode retraced: x float32[111,3] -> float32[113,3]" == said[0] and r["traces"] == 2
    parts = r["trace_s"] + r["lower_s"] + r["compile_s_backend"] + r["cache_load_s"]
    assert r["trace_s"] > 0 and parts + r["run_s"] == pytest.approx(r["compile_s"], abs=1e-9)
    # the account has both calls under the one name
    assert GLOBAL_STARTUP.snapshot()["programs"]["decode"]["calls"] >= 2


def test_a_program_no_registry_owns_appears_under_its_function_s_name():
    def draw_the_weights_of_test_startup(x):
        return jnp.tanh(x) @ x.T

    jax.block_until_ready(jax.jit(draw_the_weights_of_test_startup)(_fresh(115)))
    p = GLOBAL_STARTUP.snapshot()["programs"]["draw_the_weights_of_test_startup"]
    assert p["calls"] == 1 and p["trace_s"] > 0 and p["lower_s"] > 0 and p["run_s"] is None
    assert p["compile_s"] + p["cache_load_s"] > 0


# ------------------------------------------------------- where it is read

def test_ffmodel_compile_opens_the_spans_and_the_train_step_gets_its_lump(capsys):
    before = GLOBAL_STARTUP.snapshot()["phases"]
    model = FFModel(FFConfig(batch_size=16, epochs=1, search_budget=2, only_data_parallel=False))
    x = model.create_tensor((16, 12))
    model.softmax(model.dense(model.dense(x, 24, ActiMode.RELU), 4))
    t0 = time.monotonic()
    model.compile(optimizer=SGDOptimizer(lr=0.1), loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    compile_s = time.monotonic() - t0
    snap = GLOBAL_STARTUP.snapshot()
    grew = {k: v["total_s"] - before.get(k, {"total_s": 0.0})["total_s"] for k, v in snap["phases"].items()}
    count = {k: v["count"] - before.get(k, {"count": 0})["count"] for k, v in snap["phases"].items()}
    for name in ("search", "search.calibrate", "search.unity", "mesh", "executor", "param_init"):
        assert count[name] == 1 and grew[name] > 0.0, name
    last = {s[0]: s for s in snap["spans"] if s[0].startswith("search")}  # the newest of each name
    assert {k: s[1] for k, s in last.items() if k != "search.candidates"} == {
        "search": None, "search.calibrate": "search", "search.unity": "search"}
    args = {k: s[4] for k, s in last.items()}
    assert args["search.calibrate"] == {"calibration": "analytic"}  # the CPU backend never measures
    assert args["search.unity"]["graphs_costed"] >= 1 and args["search.unity"]["budget"] == 2
    # the parts of FFModel.compile add up to it
    named = grew["search"] + grew["mesh"] + grew["executor"] + grew["param_init"]
    assert named <= compile_s + 1e-6 and compile_s - named < 0.5
    said = capsys.readouterr().out
    assert "compiled: mesh" in said and "start-up seconds so far:" in said and "param_init" in said

    rs = np.random.RandomState(0)
    bx, by = rs.randn(16, 12).astype(np.float32), rs.randint(0, 4, size=(16,)).astype(np.int32)
    name = f"{model.executor._prog_ns}.train_step"
    model.executor.train_batch([bx], by, jax.random.key(0))
    p = next(e for e in GLOBAL_PROGRAMS.snapshot() if e["name"] == name)
    parts = p["trace_s"] + p["lower_s"] + p["compile_s_backend"] + p["cache_load_s"]
    assert p["trace_s"] > 0 and parts + p["run_s"] == pytest.approx(p["compile_s"], abs=1e-9) and p["run_s"] >= 0
    # (the second step may trace once more: the first one's outputs are committed arrays)
    model.executor.train_batch([bx], by, jax.random.key(1))
    settled = next(e for e in GLOBAL_PROGRAMS.snapshot() if e["name"] == name)
    model.executor.train_batch([bx], by, jax.random.key(2))  # warm: nothing is stamped again
    assert next(e for e in GLOBAL_PROGRAMS.snapshot() if e["name"] == name) == settled
    assert name not in GLOBAL_PROGRAMS.unstamped


def test_a_served_model_s_stats_and_debug_programs_carry_the_account():
    from flexflow_tpu.generation import GenerationEngine, SamplingParams, init_decoder_params
    from flexflow_tpu.models.transformer import TransformerConfig
    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    cfg = TransformerConfig(num_layers=1, hidden_size=32, num_heads=4, ff_size=64, seq_length=64, vocab_size=50, causal=True)
    builds = GLOBAL_STARTUP.snapshot()["phases"].get("engine_build", {"count": 0})["count"]
    engine = GenerationEngine(init_decoder_params(jax.random.key(0), cfg), cfg, max_batch_slots=2, block_size=8, prompt_buckets=[16])
    engine.generate([[1, 2, 3, 4, 5]], SamplingParams(max_new_tokens=3))
    server = InferenceServer(port=0)
    server.register_generation(GenerationModel(engine, name="lm"))
    section = server.stats()["generation"]["lm"]["startup"]
    assert section["phases"]["engine_build"]["count"] == builds + 1 and section["phases"]["import"]["count"] == 1
    assert {"origin", "now_s", "phases", "programs", "cache", "spanned_s", "spans"} <= set(section)
    assert {"requests", "hits", "misses"} <= set(section["cache"])
    assert "decode" in section["programs"] and "prefill[16]" in section["programs"]
    assert 0.0 < section["spanned_s"] <= section["now_s"]
    json.dumps(section)
    progs = {p["name"]: p for p in server.debug_programs()["models"]["lm"]["programs"]}
    for name in ("decode", "prefill[16]"):
        p = progs[name]
        parts = p["trace_s"] + p["lower_s"] + p["compile_s_backend"] + p["cache_load_s"]
        assert p["trace_s"] > 0 and parts + p["run_s"] == pytest.approx(p["compile_s"], abs=1e-9) and p["run_s"] >= 0


# ----------------------------------------- twice over one compile-cache directory

_TWICE = textwrap.dedent("""
    import json, sys
    import jax
    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from flexflow_tpu.generation import GenerationEngine, SamplingParams, init_decoder_params
    from flexflow_tpu.models.transformer import TransformerConfig
    from flexflow_tpu.obs.steptrace import GLOBAL_STARTUP

    cfg = TransformerConfig(num_layers=1, hidden_size=32, num_heads=4, ff_size=64, seq_length=64, vocab_size=50, causal=True)
    engine = GenerationEngine(init_decoder_params(jax.random.key(0), cfg), cfg, max_batch_slots=2, block_size=8, prompt_buckets=[16])
    engine.generate([[1, 2, 3, 4, 5]], SamplingParams(max_new_tokens=3))
    snap = GLOBAL_STARTUP.snapshot()
    sums = {k: sum(p[k] for p in snap["programs"].values()) for k in ("trace_s", "lower_s", "compile_s", "cache_load_s")}
    print("ACCOUNT " + json.dumps({"cache": snap["cache"], "sums": sums, "decode": snap["programs"]["decode"]}))
""")


def test_the_second_run_over_one_cache_directory_loads_what_the_first_compiled(tmp_path):
    def run():
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.pop("XLA_FLAGS", None)
        out = subprocess.run([sys.executable, "-c", _TWICE, str(tmp_path / "cache")], env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(next(line for line in out.stdout.splitlines() if line.startswith("ACCOUNT "))[8:])

    first, second = run(), run()
    for acct in (first, second):
        c = acct["cache"]
        assert c["hits"] + c["misses"] == c["requests"] > 0, c
    assert first["cache"]["misses"] > 0 and first["cache"]["hits"] == 0 and first["sums"]["compile_s"] > 0
    assert first["decode"]["cache_hit"] is False and first["decode"]["compile_s"] > 0
    assert second["cache"]["misses"] == 0 and second["cache"]["hits"] == second["cache"]["requests"] == first["cache"]["requests"]
    assert second["sums"]["compile_s"] == 0 and second["sums"]["cache_load_s"] > 0
    assert second["decode"]["cache_hit"] is True and second["decode"]["compile_s"] == 0 and second["decode"]["cache_load_s"] > 0
    assert second["sums"]["trace_s"] > 0 and second["sums"]["lower_s"] > 0  # paid again, cache or not
