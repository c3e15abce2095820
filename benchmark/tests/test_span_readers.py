"""The three span readers (``benchmark/spans.py`` and the files under
``benchmark/layers/`` that call it) on a ring built by hand, and end to
end in the CPU rehearsal of the cell."""

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
from benchmark import spans  # noqa: E402

T_OPEN, T_CLOSE = 100.0, 101.0
ENGINE, OTHER = 7, 8        # thread ids
NEW = ("engine_pass_host_ms.itl", "engine_admit_ms.itl",
       "engine_itl_p95_ms.itl")


def _rec(name, t0, ms, tid=ENGINE, **args):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": ms * 1e3, "t0": t0,
            "pid": 1, "tid": tid, "args": args}


def _ring():
    """Twelve passes 100 ms apart from 99.95 s: pass 0 straddles the
    window's opening and pass 10 its close, pass 11 lies past it. Every
    pass grants (0.2 ms), dispatches (3 ms), waits at its fence
    (60 + k ms), has 0.3 ms of its own and drains (1 ms); pass 0 admits
    one request in 30 ms, pass 4 one (a 20 ms prefill in it) in 20 ms,
    pass 7 polls a blocked head for 2 ms and admits nobody. Request "a"
    gets a token in every drain, request "b" in those of passes 5-9."""
    admit = {0: (30.0, 1), 4: (20.0, 1), 7: (2.0, 0)}
    recs = []
    for k in range(12):
        t = t0 = 99.95 + 0.1 * k
        if k in admit:
            ms, n = admit[k]
            if k == 4:
                recs.append(_rec("llm/prefill", t + 1e-4, ms - 0.2,
                                 parent="llm/admit"))
            recs.append(_rec("llm/admit", t, ms, admitted=n, prefills=n,
                             prompt_tokens=100 * n, bucket_tokens=128 * n))
            t += ms / 1e3
        for name, ms, args in (
                ("llm/grant", 0.2, {"pages": 1}),
                ("llm/dispatch", 3.0, {"fn": "llm/decode_paged",
                                       "rows": 2}),
                ("llm/fence_wait", 60.0 + k, {})):
            recs.append(_rec(name, t, ms, **args))
            t += ms / 1e3
        t += 0.3e-3
        ids = ["a", "b"] if 5 <= k <= 9 else ["a"]
        recs.append(_rec("llm/drain", t, 1.0, requests=ids,
                         finished=int(k == 9)))
        t += 1e-3
        recs.append(_rec("llm/pass", t0, (t - t0) * 1e3, step=k, rows=2,
                         admitted=admit.get(k, (0, 0))[1], prefills=0,
                         fn="llm/decode_paged"))
    # what a reader must look past: another thread's fence wait inside
    # pass 2, a record of a program that stamps no t0, a foreign name
    recs.append(_rec("llm/fence_wait", 100.16, 40.0, tid=OTHER))
    old = _rec("llm/pass", None, 50.0)
    del old["t0"]
    recs += [old, _rec("xla/compile", 100.5, 5.0, fn="x")]
    return recs


def test_pass_host_is_the_pass_less_its_fence_waits():
    # passes 1..10 start in the window: eight plain ones of 0.2 + 3 +
    # 0.3 + 1 = 4.5 ms, pass 4 with its 20 ms admission, pass 7 with
    # its 2 ms poll: (8 * 4.5 + 24.5 + 6.5) / 10
    assert spans.pass_host_ms(_ring(), T_OPEN, T_CLOSE) == \
        pytest.approx(6.7, abs=1e-6)
    assert spans.pass_host_ms(_ring(), 200.0, 201.0) is None


def test_admit_is_the_mean_over_passes_that_admitted():
    # pass 0 starts before the window, pass 7 admitted nobody
    assert spans.admit_ms(_ring(), T_OPEN, T_CLOSE) == pytest.approx(20.0)
    # with pass 0 in: (30 + 20) / 2
    assert spans.admit_ms(_ring(), 99.9, T_CLOSE) == pytest.approx(25.0)
    assert spans.admit_ms(_ring(), 100.5, T_CLOSE) is None


def test_engine_gaps_between_drain_ends_of_one_request():
    # a drain ends 0.0945 + 0.001 k (+ admission) s after its pass
    # starts: 100.0445, .1155, .2165, .3175, .4385 (pass 4: + 20 ms),
    # .5195, .6205, .7235 (pass 7: + 2 ms), .8225, .9235, 101.0245.
    # "a": nine gaps close in the window (the one that closes at
    # 101.0245 does not), "b": four, between passes 5..9
    want = sorted([71.0, 101.0, 101.0, 121.0, 81.0, 101.0, 103.0, 99.0,
                   101.0] + [101.0, 103.0, 99.0, 101.0])
    got = sorted(spans.itl_ms(_ring(), T_OPEN, T_CLOSE))
    assert got == pytest.approx(want, abs=1e-6)
    # nearest rank: ceil(0.95 * 13) = 13, the largest
    from benchmark.layers import engine_itl_p95_ms
    assert engine_itl_p95_ms._p95(_ring(), T_OPEN, T_CLOSE) == \
        pytest.approx(121.0, abs=1e-6)
    assert engine_itl_p95_ms._p95(_ring(), 300.0, 301.0) is None


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


def test_readers_find_window_and_ring_themselves(ring_in_process):
    from bigdl_tpu import observability as obs
    run = ring_in_process
    got = {n: mf.reader_of(n).read(run, n) for n in NEW}
    assert got == pytest.approx({NEW[0]: 6.7, NEW[1]: 20.0, NEW[2]: 121.0},
                                abs=1e-6)
    # a window with no pass in it; a run that reports no set-up time
    late = {"e2e": {"setup_s": run["e2e"]["setup_s"] + 50.0},
            "counters": run["counters"]}
    assert all(mf.reader_of(n).read(late, n) is None for n in NEW)
    assert all(mf.reader_of(n).read({"e2e": {}, "counters": {}}, n) is None
               for n in NEW)
    # a ring that dropped records cannot be trusted to hold the window
    obs.TRACE.dropped = 1
    assert all(mf.reader_of(n).read(run, n) is None for n in NEW)
    obs.TRACE.dropped = 0
    obs.disable()
    assert all(mf.reader_of(n).read(run, n) is None for n in NEW)
    obs.enable()
    # a program without these spans (the parent commit): nothing to read
    obs.TRACE.clear()
    obs.TRACE.append({"name": "llm/decode_step", "ph": "X", "ts": 1.0,
                      "dur": 5.0, "pid": 1, "tid": ENGINE, "args": {}})
    assert all(mf.reader_of(n).read(run, n) is None for n in NEW)


def test_manifest_entries_are_appended_for_the_engine_layer():
    man = mf.load()
    tail = man["per_layer"][-3:]
    assert tuple(m["name"] for m in tail) == NEW
    for m in tail:
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "engine",
                     "moves": "itl_p95_ms",
                     "workloads": ["mistral7b_chat_steady"]}


def test_rehearsal_runs_the_readers_end_to_end():
    """``--rehearse --trace 1`` on the CPU: the engine's own spans reach
    the readers through the real harness, the line names the metrics
    and prints no device value."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("BIGDL_TPU_OBSERVABILITY_ENABLED", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "mistral7b_chat_steady", "--rehearse", "--trace", "1",
         "--seconds", "4", "--seed", str(2 ** 31 + 25)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    for n in NEW + ("engine_host_ms.itl",):
        assert line["metrics"][n] == {"value": None, "unit": "ms"}
    said = next(ln for ln in lines if ln.startswith("# rehearsal values"))
    vals = dict(kv.split("=") for kv in said.split(": ", 1)[1].split(", "))
    assert all(float(vals[n]) > 0 for n in NEW)
    # the whole pass cannot cost less than the part of it the counter
    # brackets
    assert float(vals[NEW[0]]) >= float(vals["engine_host_ms.itl"])
