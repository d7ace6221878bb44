"""The control procedure of bench/control.py at a size a test run holds.

On the chip the control (the reference one precision step down: the
correlation's matmul at ``high``, the partial correlations in float32)
is read at each cell's own size, through the whole run and the cell's
limits; those readings set the upper ends of the limits in bench/limits/.
The CPU's float32 matmul ignores the precision setting, so here the
control is the float32 reference, and the test checks that the roles run
through the same run and limits as the benchmark: the program reads
correct, the planted fault (one kept edge reported removed) reads not
correct, and the control prints a full result line.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, registry  # noqa: E402
from test_bench_harness import LIMITS, make_root  # noqa: E402


@pytest.fixture
def jnp_pc(monkeypatch):
    """The program's pc on the jnp engine: the procedure, not the kernels,
    is under test here."""
    from repro import core

    real = core.pc

    def pc(x, **kw):
        return real(x, **{**kw, "engine": "S", "corr": "jnp"})

    monkeypatch.setattr(core, "pc", pc)
    return pc


def test_roles_run_through_the_harness(tmp_path, capsys, jnp_pc):
    root = make_root(tmp_path)
    rc = control.main(["--workload", "tiny.tiny_mix", "--as", "program", "flip", "control",
                       "--seeds", str(2**31 + 77)], root=root, need_chip=False)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert [ln.split(" as ")[1] for ln in lines[0::2]] == [
        f"{r}, seed {2**31 + 77}" for r in ("program", "flip", "control")]
    prog, flip, ctl = (json.loads(ln) for ln in lines[1::2])
    assert prog["correct"] is True and prog["attempted"] == 2
    assert flip["correct"] is False and flip["checks"]["z_gap"]["value"] > LIMITS["z_gap"]
    assert set(ctl["checks"]) == set(prog["checks"]) and ctl["failed"] == 0
    assert ctl["checks"]["bad"]["value"] == 0


def test_control_output_layout(jnp_pc):
    from bench.data.gaussian_dag import sample

    x = sample(30, 200, 0.2, 5).astype(np.float32)
    cfg, mix = {"alpha": 0.01}, {"max_level": None, "sepset_depth": 8, "orient": True}
    out = registry.load_call({}).control_output(x, cfg, mix)
    prog = jnp_pc(x, alpha=0.01)
    assert out.sepsets.shape == prog.sepsets.shape and out.adj.dtype == bool
    assert (out.adj == prog.adj).all()

