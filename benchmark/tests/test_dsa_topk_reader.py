"""CPU checks of ``dsa_topk_dev_ms.itl``, the selection kernel's device
milliseconds a decode step in ``glm5_longctx_steady``: its arithmetic
on a hand-built run (``test_glm5_cell``'s), nothing from a run whose
decode program selects by a sort, and its manifest entry."""

import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest as mf  # noqa: E402
from benchmark.tests.test_glm5_cell import CELL, MODEL, _run  # noqa: E402

NAME = "dsa_topk_dev_ms.itl"
KERNEL = "jit_step:dsa_topk_decode[24x16x128]"


def _ops(**extra):
    """The hand-built run's ops with the sort swapped for the kernel:
    0.3 ms of it a step over 200 steps."""
    ops = {k: v for k, v in _run()["trace"]["devices"][0]["ops"].items()
           if "sort[24x53248]" not in k}
    return {**ops, KERNEL: 200 * 0.0003, **extra}


def test_reads_the_kernel_a_step():
    reader = mf.reader_of(NAME)
    assert reader.read(_run(ops=_ops()), NAME) == pytest.approx(0.3)
    # the kernel's ops in another program are not the decode step's
    assert reader.read(_run(ops=_ops(**{
        "jit_build:dsa_topk_decode[24x16x128]": 5.0})), NAME) == \
        pytest.approx(0.3)
    # and the sort's reader finds nothing once the sort is gone
    sort = "dsa_select_dev_ms.itl"
    assert mf.reader_of(sort).read(_run(ops=_ops()), sort) is None


def test_reads_nothing_without_the_kernel():
    reader = mf.reader_of(NAME)
    assert reader.read(_run(), NAME) is None          # the parent's sort
    assert reader.read(_run(ops=_ops(), counters={}), NAME) is None
    assert reader.read({**_run(ops=_ops()), "trace": None}, NAME) is None
    other = {**_run(ops=_ops()),
             "model": dataclasses.replace(MODEL, index_topk=0)}
    assert reader.read(other, NAME) is None


def test_manifest_entry():
    (m,) = [e for e in mf.load()["per_layer"] if e["name"] == NAME]
    assert m == {"name": NAME, "unit": "ms", "better": "lower",
                 "source": "device_trace", "layer": "kernels",
                 "moves": "itl_p95_ms", "workloads": [CELL]}
    assert os.path.exists(os.path.join(ROOT, mf.reader_path(NAME)))
