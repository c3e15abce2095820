"""The four readers of ISSUE 35 (``benchmark/spans_admission.py`` and
the files under ``benchmark/layers/`` that call it) on rings built by
hand, their manifest entries looked up by name, and end to end in the
CPU rehearsal of every cell."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest as mf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark import spans_admission as sa  # noqa: E402

T_OPEN, T_CLOSE = 100.0, 101.0
ENGINE, OTHER = 7, 8        # thread ids
NEW = ("engine_stage_ms.itl", "engine_prefill_finish_ms.itl",
       "gaps_behind_prefill_pct.itl", "py_gc_ms.itl")
CELLS = ("mistral7b_chat_steady", "kanana2_longgen_steady",
         "mimo_v25_mixedlen_steady", "brumby14b_reasoning_steady")


def _rec(name, t0, ms, tid=ENGINE, **args):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": ms * 1e3, "t0": t0,
            "pid": 1, "tid": tid, "args": args}


def _prefill(t, stage, call, finish, rid, tid=ENGINE):
    """The three children of one whole-prompt prefill from ``t``."""
    return [
        _rec("llm/prefill_stage", t, stage, tid, request=rid, bucket=256,
             transfers=8),
        _rec("llm/prefill_dispatch", t + stage / 1e3, call, tid,
             request=rid, bucket=256, fn="llm/prefill_ragged"),
        _rec("llm/prefill_finish", t + (stage + call) / 1e3, finish, tid,
             request=rid, updates=3)]


def _ring():
    """Five sweeps on the engine thread. One straddles the window's
    opening (starts before it: not counted, though its finish starts
    inside), one seats a request (sweep 1 ms, stage 4, call 2, finish
    30), one seats TWO (sweep 1, stage 5, call 3, finish 10, then stage
    6, call 1, finish 20: the device has work at the first call's end),
    one polls a blocked head and prefills nobody, one starts inside the
    window and ends past it. Another thread's engine admits too. Six
    drains: the one that ends before the window and the one that ends
    after it carry gaps that do not count."""
    recs = []

    def sweep(t, ms, prefills, tid=ENGINE):
        recs.append(_rec("llm/admit", t, ms, tid, admitted=prefills,
                         prefills=prefills, prompt_tokens=0,
                         bucket_tokens=0))

    sweep(99.99, 40.0, 1)
    recs += _prefill(99.991, 4.0, 2.0, 8.0, "early")   # finish from 99.997
    recs += [_rec("llm/prefill_finish", 100.0005, 8.0, request="early2",
                  updates=3)]                         # a chunk's final one
    sweep(100.1, 37.0, 1)
    recs += _prefill(100.101, 4.0, 2.0, 30.0, "a")
    sweep(100.3, 46.0, 2)
    recs += _prefill(100.301, 5.0, 3.0, 10.0, "b")
    recs += _prefill(100.319, 6.0, 1.0, 20.0, "c")
    sweep(100.5, 2.0, 0)
    sweep(100.99, 30.0, 1)
    recs += _prefill(100.991, 9.0, 2.0, 17.0, "late")
    # another engine in the process: its own sweep, its own children
    sweep(100.1005, 50.0, 1, tid=OTHER)
    recs += _prefill(100.1006, 1.0, 1.0, 40.0, "x", tid=OTHER)
    for t, ms, gaps, behind in ((99.9, 50.0, 100, 100),   # ends 99.95
                                (99.99, 20.0, 10, 1),     # ends 100.01
                                (100.2, 1.0, 20, 0),
                                (100.4, 1.0, 30, 2),
                                (100.6, 1.0, 40, 0),
                                (100.995, 10.0, 7, 7)):   # ends 101.005
        recs.append(_rec("llm/drain", t, ms, requests=["a"], finished=0,
                         gaps=gaps, gaps_behind_prefill=behind,
                         eager_us=0.0))
    recs += [_rec("py/gc", 99.5, 90.0, generation=2, collected=5),
             _rec("py/gc", 100.7, 80.0, tid=OTHER, generation=2,
                  collected=9),
             _rec("py/gc", 100.8, 0.75, generation=1, collected=0),
             _rec("py/gc", 101.2, 70.0, generation=2, collected=1),
             _rec("xla/compile", 100.5, 5.0, fn="x")]
    return recs


def test_stage_runs_from_the_sweep_to_the_first_calls_end():
    # sweeps that start in the window and prefilled: engine 1 + 4 + 2,
    # engine 1 + 5 + 3 (the second prefill of that sweep is not the
    # first), engine 1 + 9 + 2 (it ends past the window, it started in
    # it), the other thread's 0.1 + 1 + 1
    want = (7.0 + 9.0 + 12.0 + 2.1) / 4
    assert sa.stage_ms(_ring(), T_OPEN, T_CLOSE) == \
        pytest.approx(want, abs=1e-6)
    # with the straddling sweep in: 1 + 4 + 2 more
    assert sa.stage_ms(_ring(), 99.9, T_CLOSE) == \
        pytest.approx((want * 4 + 7.0) / 5, abs=1e-6)
    assert sa.stage_ms(_ring(), 100.4, 100.9) is None
    # a sweep whose prefills were all chunked has no call to end at
    only = [r for r in _ring() if r["name"] != "llm/prefill_dispatch"]
    assert sa.stage_ms(only, T_OPEN, T_CLOSE) is None


def test_finish_is_the_mean_of_those_that_start_inside():
    # "early"'s started at 99.997 and "late"'s starts at 101.002;
    # "early2" (a chunk's final one, no sweep around it), a, b, c and
    # the other thread's x start inside
    want = (8.0 + 30.0 + 10.0 + 20.0 + 40.0) / 5
    assert sa.prefill_finish_ms(_ring(), T_OPEN, T_CLOSE) == \
        pytest.approx(want, abs=1e-6)
    assert sa.prefill_finish_ms(_ring(), 200.0, 201.0) is None


def test_gaps_count_in_the_drain_that_ends_inside():
    assert sa.gaps_behind_prefill_pct(_ring(), T_OPEN, T_CLOSE) == \
        pytest.approx(100.0 * 3 / 100)
    assert sa.gaps_behind_prefill_pct(_ring(), 99.0, 100.0) == \
        pytest.approx(100.0)
    assert sa.gaps_behind_prefill_pct(_ring(), 200.0, 201.0) is None
    # the parent's drains carry no counts
    bare = [_rec("llm/drain", 100.2, 1.0, requests=["a"], finished=0)]
    assert sa.gaps_behind_prefill_pct(bare, T_OPEN, T_CLOSE) is None


def test_collections_of_any_thread_that_start_inside():
    assert sa.gc_ms(_ring(), T_OPEN, T_CLOSE) == pytest.approx(80.75)
    assert sa.gc_ms(_ring(), 200.0, 201.0) == 0.0


@pytest.fixture
def ring_in_process():
    """The hand-built ring as this process's trace ring, and a ``run``
    whose window is [100, 101) on the clock ``T_START`` was read from."""
    from bigdl_tpu import observability as obs
    was, kept = obs.enabled(), obs.TRACE.spans()
    obs.enable()
    obs.TRACE.clear()
    for r in _ring():
        obs.TRACE.append(r)
    yield {"e2e": {"setup_s": T_OPEN - bench_run.T_START},
           "counters": {"t": T_CLOSE - T_OPEN}}
    obs.TRACE.clear()
    for r in kept:
        obs.TRACE.append(r)
    (obs.enable if was else obs.disable)()


def test_readers_find_window_and_ring_themselves(ring_in_process,
                                                 monkeypatch, capsys):
    from bigdl_tpu import observability as obs
    from bigdl_tpu.observability import tracing
    run = ring_in_process
    monkeypatch.setattr(tracing, "gc_collections_total", [12, 3, 1])

    def read_all(r=run):
        return {n: mf.reader_of(n).read(r, n) for n in NEW}

    assert read_all() == pytest.approx(
        {NEW[0]: 7.525, NEW[1]: 21.6, NEW[2]: 3.0, NEW[3]: 80.75},
        abs=1e-6)
    said = capsys.readouterr().out.strip().splitlines()
    # "late"'s call and finish and the last collection begin past it
    n = len(_ring())
    assert said == [f"# trace ring: {n} of {obs.TRACE.capacity} records "
                    f"({100.0 * n / obs.TRACE.capacity:.1f} %), {n - 3} of "
                    "them begun before the window closed; dropped 0"]
    # a window with nothing in it: no collection is a reading, 0.0
    late = {"e2e": {"setup_s": run["e2e"]["setup_s"] + 50.0},
            "counters": run["counters"]}
    assert read_all(late) == {NEW[0]: None, NEW[1]: None, NEW[2]: None,
                              NEW[3]: 0.0}
    assert set(read_all({"e2e": {}, "counters": {}}).values()) == {None}
    # a ring that dropped records cannot be trusted to hold the window
    obs.TRACE.dropped = 1
    assert set(read_all().values()) == {None}
    assert capsys.readouterr().out.strip().endswith("dropped 1")
    obs.TRACE.dropped = 0
    obs.disable()
    assert set(read_all().values()) == {None}
    obs.enable()
    # a program that watches no collector (the parent commit) or whose
    # watcher never ran: 0.0 would be a reading it did not take
    monkeypatch.setattr(tracing, "gc_collections_total", [0, 0, 0])
    assert mf.reader_of(NEW[3]).read(run, NEW[3]) is None
    monkeypatch.delattr(tracing, "gc_collections_total")
    assert mf.reader_of(NEW[3]).read(run, NEW[3]) is None
    # and none of these spans, the old drains: nothing to read
    obs.TRACE.clear()
    for r in (_rec("llm/admit", 100.1, 37.0, admitted=1, prefills=1),
              _rec("llm/drain", 100.2, 1.0, requests=["a"], finished=0)):
        obs.TRACE.append(r)
    assert set(read_all().values()) == {None}


def test_manifest_entries_by_name():
    man = mf.load()
    by = {m["name"]: m for m in man["per_layer"]}
    for n, unit in zip(NEW, ("ms", "ms", "%", "ms")):
        assert by[n] == {"name": n, "unit": unit, "better": "lower",
                         "source": "program_span", "layer": "engine",
                         "moves": "itl_p95_ms", "workloads": list(CELLS)}
        assert os.path.exists(os.path.join(ROOT, mf.reader_path(n)))
    # what they split stays until a benchmark issue retires it
    assert by["engine_admit_ms.itl"]["workloads"] == list(CELLS)
    for cell in CELLS:
        mine = [m["name"] for m in mf.metrics_for(man, "per_layer", cell)]
        assert set(NEW) <= set(mine)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_reads_all_four_in_every_cell(cell):
    """``--rehearse --trace 1`` on the CPU: the engine's new spans and
    counts reach the readers through the real harness in every cell,
    beside the metrics the cell printed before; the ring held the
    run."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("BIGDL_TPU_OBSERVABILITY_ENABLED", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--rehearse", "--trace", "1",
         "--seconds", "4", "--seed", str(2 ** 31 + 35)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    for n, unit in zip(NEW, ("ms", "ms", "%", "ms")):
        assert line["metrics"][n] == {"value": None, "unit": unit}
    for n in ("engine_admit_ms.itl", "engine_pass_host_ms.itl",
              "engine_itl_p95_ms.itl", "engine_host_ms.itl"):
        assert n in line["metrics"]
    said = next(ln for ln in lines if ln.startswith("# rehearsal values"))
    vals = {k: float(v) for k, v in (
        kv.split("=") for kv in said.split(": ", 1)[1].split(", "))}
    assert vals[NEW[0]] > 0 and vals[NEW[1]] > 0
    assert 0 <= vals[NEW[2]] <= 100 and vals[NEW[3]] >= 0
    # the two that split an admission lie inside it
    assert vals[NEW[0]] + vals[NEW[1]] <= vals["engine_admit_ms.itl"] * 1.001
    fill = next(ln for ln in lines if ln.startswith("# trace ring: "))
    assert fill.endswith("dropped 0")
    assert float(fill.split("(")[1].split(" %")[0]) < 80.0
