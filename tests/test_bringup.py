"""ISSUE 21 (bring-up on the v5e under jax 0.9): what a CPU run can pin.

The chip itself is exercised by ``chip_smoke.py`` and ``tests_tpu/``;
here are the properties that make those honest — the compile cache is
placed from outside, importing takes no chip, a lost chip and a failing
engine pass are errors instead of fallbacks. Small on purpose: tier-1
has little time to spare.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from bigdl_tpu import reliability
from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
from bigdl_tpu.llm.serving import LLMServer
from bigdl_tpu.utils.conf import conf
from bigdl_tpu.utils.engine import Engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: imports EVERY module of the package (and the load generator), then
#: says where the compile cache is and whether any backend came up
_PROBE = """
import importlib, os
import bigdl_tpu, tools.loadgen
root = os.path.dirname(bigdl_tpu.__file__)
for d, _, files in os.walk(root):
    for f in files:
        if f.endswith(".py"):
            rel = os.path.relpath(os.path.join(d, f), os.path.dirname(root))
            importlib.import_module(
                rel[:-3].replace(os.sep, ".").removesuffix(".__init__"))
import jax
from jax._src import xla_bridge
print(jax.config.jax_compilation_cache_dir)
print(xla_bridge.backends_are_initialized())
"""


def _env(**over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")}
    env.update(JAX_PLATFORMS="cpu", **over)
    return env


@pytest.fixture(scope="module")
def children(tmp_path_factory):
    """The four child processes these tests read, run side by side
    (each is mostly import time): the probe from a foreign working
    directory with the cache variable unset and set, and chip_smoke.py
    in the checkout and alone in an empty directory."""
    away = tmp_path_factory.mktemp("away")
    bare = tmp_path_factory.mktemp("bare")
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (bare / "chip_smoke.py").write_text(f.read())
    elsewhere = str(away / "cache")
    spawn = {
        "unset": ([sys.executable, "-c", _PROBE], str(away),
                  _env(PYTHONPATH=REPO)),
        "set": ([sys.executable, "-c", _PROBE], str(away),
                _env(PYTHONPATH=REPO,
                     JAX_COMPILATION_CACHE_DIR=elsewhere)),
        "smoke": ([sys.executable, "chip_smoke.py"], REPO, _env()),
        "bare": ([sys.executable, "chip_smoke.py"], str(bare), _env()),
    }
    procs = {name: subprocess.Popen(argv, cwd=cwd, env=env, text=True,
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE)
             for name, (argv, cwd, env) in spawn.items()}
    out = {"elsewhere": elsewhere}
    for name, proc in procs.items():
        stdout, stderr = proc.communicate(timeout=180)
        out[name] = (proc.returncode, stdout, stderr)
    return out


class TestCompileCachePlacement:
    def test_unset_is_the_checkout_whatever_the_cwd(self, children):
        """Unset: ``<checkout>/.jax_cache`` from a process started
        somewhere else, the same as in this one — and importing every
        ``bigdl_tpu`` module and the load generator initialised no
        backend (a parent that imports them leaves the chip to its
        children)."""
        rc, stdout, stderr = children["unset"]
        assert rc == 0, stderr
        assert stdout.split() == [os.path.join(REPO, ".jax_cache"),
                                  "False"]
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            assert jax.config.jax_compilation_cache_dir == \
                os.path.join(REPO, ".jax_cache")

    def test_env_set_code_sets_nothing(self, children):
        rc, stdout, stderr = children["set"]
        assert rc == 0, stderr
        assert stdout.split() == [children["elsewhere"], "False"]


class TestNoHiddenFallback:
    def test_explicit_tpu_engine_on_cpu_raises(self):
        """``engine_type`` used to be a label: "tpu" on this host built
        a CPU mesh called "tpu"."""
        assert jax.default_backend() == "cpu"
        Engine.reset()
        try:
            with pytest.raises(RuntimeError, match="'tpu' was requested"):
                Engine.init(engine_type="tpu")
            assert not Engine.is_initialized()
            conf.set("bigdl.engine.type", "tpu")
            try:
                with pytest.raises(RuntimeError, match="backend is 'cpu'"):
                    Engine.init()
            finally:
                conf.unset("bigdl.engine.type")
            Engine.init(engine_type="cpu")     # orca's local-cpu mode
            assert Engine.config().engine_type == "cpu"
        finally:
            Engine.reset()

    def test_chip_smoke_refuses_cpu_and_a_bare_directory(self, children):
        rc, stdout, stderr = children["smoke"]
        assert rc != 0
        assert "platform=cpu" in stderr and "no TPU" in stderr
        assert '"ok"' not in stdout
        # alone, without the package: fails as well, prints no result
        rc, stdout, stderr = children["bare"]
        assert rc != 0 and '"ok"' not in stdout

    def test_bench_reports_phase_errors(self):
        import bench
        res = {"metric": "x", "extra": {
            "a": {"value": 1}, "b": {"error": "ValueError('boom')"},
            "telemetry": {"chaos_all": {"error": "RuntimeError()"}}}}
        assert bench._phase_errors(res) == [
            ("result.extra.b", "ValueError('boom')"),
            ("result.extra.telemetry.chaos_all", "RuntimeError()")]
        assert bench._phase_errors({"extra": {"a": {"value": 1}}}) == []

    def test_native_library_named_by_source_hash(self):
        from bigdl_tpu.native import build
        assert "-march=native" not in build._CXXFLAGS
        name = os.path.basename(build._out_path())
        assert name.startswith("libbigdl_tpu_quant-") and \
            name != "libbigdl_tpu_quant.so"


@pytest.fixture(scope="module")
def tiny_model():
    return LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                        max_cache_len=64)


class TestEnginePassFailures:
    """The engine loop used to count every exception from a pass and
    retry it forever, silently: a kernel Mosaic refuses showed up as a
    ``get()`` that timed out."""

    PROMPT = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)

    def _serve_under(self, model, plan):
        # the request is queued before the engine starts, so the first
        # pass admits it and its step meets the armed fault
        srv = LLMServer(model, max_batch=2, max_seq_len=32)
        req = srv.submit(self.PROMPT, max_new_tokens=5)
        reliability.set_plan(plan)
        try:
            srv.start()
            try:
                return srv, req, req.get(timeout=300)
            finally:
                srv.stop()
        finally:
            reliability.set_plan(None)

    def test_deterministic_error_fails_the_request(self, tiny_model):
        plan = reliability.FaultPlan(seed=0)
        plan.add("llm.step", "raise", times=1,
                 exc=ValueError("block shape (3, 100) not tiled"))
        with pytest.raises(RuntimeError, match="not tiled") as ei:
            self._serve_under(tiny_model, plan)
        assert "ValueError" in str(ei.value)

    def test_same_error_twice_fails_the_request(self, tiny_model):
        plan = reliability.FaultPlan(seed=0)
        plan.add("llm.step", "raise", times=None,
                 exc=OSError("device lost"))
        with pytest.raises(RuntimeError, match="device lost"):
            self._serve_under(tiny_model, plan)

    def test_injected_fault_is_still_retried(self, tiny_model):
        want = tiny_model.generate(self.PROMPT[None],
                                   max_new_tokens=5)[0, 8:]
        plan = reliability.FaultPlan(seed=0)
        plan.add("llm.step", "raise", times=3)
        srv, req, got = self._serve_under(tiny_model, plan)
        assert plan.fired.count(("llm.step", "raise")) == 3
        assert srv.pass_errors == 3 and req.error is None
        np.testing.assert_array_equal(np.asarray(got), want)
