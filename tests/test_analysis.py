"""Static-analysis suite (repro/analysis/): per-rule fixtures, analyzer
regressions, and the baseline ratchet.

Every rule class gets a violation fixture that fires EXACTLY ONCE and a
clean twin that fires zero times — so a rule that silently stops firing
(or starts double-reporting) fails here before it can let a real
regression through.  On top of the fixtures:

* a jaxpr regression pinning the S-kernel chunk path at zero promotions,
  zero callbacks, and exactly its declared pallas_call count;
* a Pallas write-race regression on a deliberately broken toy kernel
  (blind overwrite of a revisited output block);
* the two-sided baseline ratchet: an unbaselined finding fails AND a
  stale baseline entry fails;
* README badge / rule-catalog sync.
"""
from __future__ import annotations

import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import baseline as B
from repro.analysis import jaxpr as J
from repro.analysis import pallas as PA
from repro.analysis import rules as R
from repro.analysis.findings import RULE_CATALOG, Finding

pytestmark = pytest.mark.analysis

ROOT = Path(__file__).resolve().parent.parent


def _codes(findings):
    return [f.code for f in findings]


def _check(src, path="src/repro/core/mod.py", allowlist=None):
    return R.check_source(textwrap.dedent(src), path,
                          allowlist={} if allowlist is None else allowlist)


# --------------------------------------------------------------- layer 1
class TestRPR001:
    VIOLATION = """
    import jax

    @jax.jit
    def step(x):
        lr = x.mean().item()
        return x * lr
    """

    CLEAN = """
    import jax

    @jax.jit
    def step(x):
        return x * x.mean()
    """

    def test_fires_once(self):
        assert _codes(_check(self.VIOLATION)) == ["RPR001"]

    def test_clean_twin(self):
        assert _check(self.CLEAN) == []

    def test_traced_operand_of_combinator(self):
        src = """
        import jax

        def body(i, x):
            return x + float(x.sum())

        def run(x):
            return jax.lax.fori_loop(0, 4, body, x)
        """
        fs = _check(src)
        assert _codes(fs) == ["RPR001"]
        assert fs[0].detail == "float()"

    def test_obs_fetch_in_jitted_body_fires_once(self):
        src = """
        import jax
        from repro import obs

        @jax.jit
        def step(x):
            return x * obs.fetch(x.max(), site="step")
        """
        fs = _check(src)
        assert _codes(fs) == ["RPR001"]
        assert fs[0].detail == "obs.fetch"


class TestRPR002:
    VIOLATION = """
    import jax

    def collect(x):
        return jax.device_get(x)
    """

    def test_fires_once(self):
        fs = _check(self.VIOLATION)
        assert _codes(fs) == ["RPR002"]
        assert fs[0].key == "RPR002 src/repro/core/mod.py::collect::device_get"

    def test_clean_when_allowlisted(self):
        key = "RPR002 src/repro/core/mod.py::collect::device_get"
        assert _check(self.VIOLATION, allowlist={key: "test seam"}) == []

    def test_launch_is_exempt(self):
        assert _check(self.VIOLATION, path="src/repro/launch/mod.py") == []

    def test_asarray_pair_collapses_to_one_key(self):
        src = """
        import jax
        import numpy as np

        def materialize(x):
            return np.asarray(jax.device_get(x))
        """
        fs = _check(src)
        assert _codes(fs) == ["RPR002"]
        assert fs[0].detail == "np.asarray(device_get)"

    def test_obs_fetch_is_a_seam(self):
        src = """
        import numpy as np
        from repro import obs

        def degree(adj):
            return int(obs.fetch(adj.sum(1).max(), site="degree"))

        def materialize(x):
            return np.asarray(obs.fetch(x, site="readback"))
        """
        fs = _check(src)
        assert _codes(fs) == ["RPR002", "RPR002"]
        assert [f.key for f in fs] == [
            "RPR002 src/repro/core/mod.py::degree::obs.fetch",
            "RPR002 src/repro/core/mod.py::materialize::np.asarray(obs.fetch)"]


class TestRPR003:
    VIOLATION = """
    import time

    def tick():
        return time.perf_counter()
    """

    def test_fires_once(self):
        assert _codes(_check(self.VIOLATION)) == ["RPR003"]

    def test_obs_is_the_sanctioned_home(self):
        assert _check(self.VIOLATION, path="src/repro/obs/clock.py") == []

    def test_bare_import_alias_counts(self):
        src = "from time import perf_counter\n"
        assert _codes(_check(src)) == ["RPR003"]


class TestRPR004:
    VIOLATION = """
    def my_kernel(x, *, interpret: bool = False):
        return x
    """

    CLEAN = """
    def my_kernel(x, *, interpret=None):
        return x
    """

    def test_fires_once(self):
        fs = _check(self.VIOLATION, path="src/repro/kernels/mod.py")
        assert _codes(fs) == ["RPR004"]

    def test_clean_twin(self):
        assert _check(self.CLEAN, path="src/repro/kernels/mod.py") == []

    def test_rogue_resolver_definition(self):
        src = "def resolve_interpret(flag):\n    return bool(flag)\n"
        fs = _check(src, path="src/repro/kernels/mod.py")
        assert _codes(fs) == ["RPR004"]
        # backend.py is the one sanctioned definition site
        assert _check(src, path="src/repro/kernels/backend.py") == []


class TestRPR005:
    VIOLATION = """
    import jax

    step = jax.jit(lambda x, mode: x, static_argnames=("mode",))
    """

    CLEAN = """
    import jax

    step = jax.jit(lambda x, ell: x, static_argnames=("ell",))
    """

    def test_fires_once(self):
        fs = _check(self.VIOLATION)
        assert _codes(fs) == ["RPR005"]
        assert fs[0].detail == "static_argnames:mode"

    def test_clean_twin(self):
        assert _check(self.CLEAN) == []

    def test_bare_lru_cache(self):
        src = """
        import functools

        @functools.lru_cache
        def plan(n):
            return n
        """
        assert _codes(_check(src)) == ["RPR005"]


# --------------------------------------------------------------- layer 2
class TestRPR101:
    def test_fires_once(self):
        import numpy as np

        def promote(x):
            return x + np.float64(1.0)

        import jax.numpy as jnp

        fs = J.promotion_findings(promote, jnp.zeros((4,), jnp.float32))
        assert _codes(fs) == ["RPR101"]

    def test_clean_twin(self):
        import jax.numpy as jnp

        def stay_f32(x):
            return x + jnp.float32(1.0)

        assert J.promotion_findings(stay_f32, jnp.zeros((4,), jnp.float32)) == []


class TestRPR102:
    def test_fires_once(self):
        import jax
        import jax.numpy as jnp

        def chatty(x):
            jax.debug.print("x = {}", x)
            return x + 1

        fs = J.callback_findings(chatty, jnp.zeros((4,), jnp.float32))
        assert _codes(fs) == ["RPR102"]

    def test_clean_twin(self):
        import jax.numpy as jnp

        assert J.callback_findings(lambda x: x + 1,
                                   jnp.zeros((4,), jnp.float32)) == []


class TestRPR103:
    def test_kernel_count_fires_once(self):
        import jax.numpy as jnp

        fs = J.kernel_count_findings(lambda x: x + 1, 1,
                                     jnp.zeros((4,), jnp.float32))
        assert _codes(fs) == ["RPR103"]

    def test_kernel_count_clean(self):
        import jax.numpy as jnp

        assert J.kernel_count_findings(lambda x: x + 1, 0,
                                       jnp.zeros((4,), jnp.float32)) == []

    def test_stats_contract_fires_on_broken_chunks(self):
        stats = [{"engine": "S", "total_sets": 100, "n_chunk": 32,
                  "chunks": 3, "dispatches": 3, "pipeline_depth": 1}]
        fs = J.stats_contract_findings(stats)  # ceil(100/32) = 4, not 3
        assert _codes(fs) == ["RPR103"]

    def test_stats_contract_fires_on_pipeline_multiplier(self):
        stats = [{"engine": "S", "total_sets": 64, "n_chunk": 32,
                  "chunks": 2, "dispatches": 2, "pipeline_depth": 2}]
        fs = J.stats_contract_findings(stats)  # pipelined => 2 * 2 = 4
        assert _codes(fs) == ["RPR103"]

    def test_stats_contract_fires_on_class_chunks(self):
        stats = [{"engine": "S-kernel", "total_sets": 820, "chunks": 3,
                  "dispatches": 4, "classes": [{"chunks": 1}, {"chunks": 1}]}]
        fs = J.stats_contract_findings(stats)  # its classes ran 2 chunks
        assert _codes(fs) == ["RPR103"]

    def test_stats_contract_clean(self):
        stats = [
            {"engine": "S", "total_sets": 100, "n_chunk": 32, "chunks": 4,
             "dispatches": 4, "pipeline_depth": 1},
            {"engine": "S", "total_sets": 64, "n_chunk": 32, "chunks": 2,
             "dispatches": 4, "pipeline_depth": 2},
            # a degree-class level: its classes' chunks and one commit
            {"engine": "S-kernel", "total_sets": 820, "chunks": 3,
             "dispatches": 4, "classes": [{"chunks": 1}, {"chunks": 2}]},
            {"skipped": True},
        ]
        assert J.stats_contract_findings(stats) == []


class TestRPR104:
    def test_fires_on_overflowing_plan(self):
        # a planner that happily accepts a level whose doubled worst commit
        # key (rank*2+1) passes the imax sentinel — the exact bug class the
        # rule exists for
        def leaky_plan(npr, ell, n_rows):
            from math import comb

            return npr, 64, comb(npr, ell)

        fs = J.rank_capacity_findings(plan_fn=leaky_plan, n_max=50, l_max=8)
        assert fs and set(_codes(fs)) == {"RPR104"}

    def test_real_planner_is_clean(self):
        # levels.plan_level must refuse every plan whose commit keys could
        # alias (guard tightened to imax // 2 after this analyzer found the
        # factor-2 gap)
        assert J.rank_capacity_findings(n_max=64, l_max=8) == []

    def test_guard_raises_in_the_gap_region(self):
        # C(47, 8) = 314 457 495 fits int32 ranks but NOT doubled commit
        # keys: the planner must refuse instead of silently not committing
        from repro.core import levels as L

        with pytest.raises(ValueError, match="commit-key capacity"):
            L.plan_level(47, 8, n_rows=8)


def test_skernel_entry_contract_regression():
    """The S-kernel chunk path: zero f64 promotions, zero callbacks, and
    exactly its declared pallas_call count (cholinv + cisweep = 2)."""
    entry = next(e for e in J.entry_points() if e.name == "chunk_s_kernel")
    assert entry.pallas_calls == 2
    fn, args, kwargs = entry.build()
    assert J.promotion_findings(fn, *args, name=entry.name, **kwargs) == []
    assert J.callback_findings(fn, *args, name=entry.name, **kwargs) == []
    assert J.count_pallas_calls(fn, *args, **kwargs) == 2


def test_entry_registry_covers_every_engine():
    """Every registered PC engine's traced surface has an analysis entry."""
    names = {e.name for e in J.entry_points()}
    assert {"chunk_s", "chunk_e", "chunk_s_kernel", "chunk_s_kernel_class", "chunk_s_grid",
            "chunk_g2", "chunk_g2_kernel", "level1_dense",
            "pc_scan"} <= names


# --------------------------------------------------------------- layer 3
def _toy_clobber_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0  # blind overwrite — no guard, no RMW


def _toy_clobber(x):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n, m = x.shape
    return pl.pallas_call(
        _toy_clobber_kernel,
        grid=(n // 8, m // 128),
        in_specs=[pl.BlockSpec((8, 128), lambda i, k: (i, k))],
        out_specs=pl.BlockSpec((8, 128), lambda i, k: (i, 0)),  # ignores k
        out_shape=jax.ShapeDtypeStruct((n, 128), jnp.float32),
        interpret=True,
    )(x)


class TestPallasChecks:
    def _shape(self, *s):
        import jax
        import jax.numpy as jnp

        return jax.ShapeDtypeStruct(s, jnp.float32)

    def test_write_race_on_broken_toy_kernel(self):
        fs = PA.check_kernel(_toy_clobber, self._shape(16, 256),
                             name="toy", path="<toy>")
        assert _codes(fs) == ["RPR202"]
        assert "clobber" in fs[0].detail

    def test_coverage_hole_fires(self):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def holey(x):
            return pl.pallas_call(
                _toy_clobber_kernel,
                grid=(1,),  # produces only block (0, 0) of a 2-block output
                in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
                interpret=True,
            )(x)

        fs = PA.check_kernel(holey, self._shape(16, 128),
                             name="holey", path="<toy>")
        assert _codes(fs) == ["RPR201"]

    def test_vmem_budget_fires(self):
        fs = PA.check_kernel(_toy_clobber, self._shape(16, 256),
                             name="toy", path="<toy>", budget=1024)
        assert "RPR203" in _codes(fs)

    def test_sgrid_accumulation_is_recognized_as_safe(self):
        """sgrid revisits t_win/s_win across rank steps but RMWs them —
        the analyzer must NOT flag the sanctioned reduction pattern."""
        case = next(c for c in PA.kernel_cases() if c[0] == "sgrid_kernel")
        fn, args, kwargs = case[2]()
        calls = PA.capture_calls(fn, *args, **kwargs)
        assert len(calls) == 1 and calls[0].grid[-1] > 1  # really revisits
        assert PA.check_call(calls[0], "sgrid_kernel", case[1]) == []

    def test_registry_covers_all_kernels(self):
        names = {c[0] for c in PA.kernel_cases()}
        assert names == {"sgrid_kernel", "cholinv_kernel", "cisweep_kernel",
                         "level1_dense_kernel", "gsq_cells", "level0_kernel",
                         "corr_matmul"}


# --------------------------------------------------------------- baseline
class TestBaselineRatchet:
    F = Finding(code="RPR002", path="src/repro/core/mod.py", line=3,
                message="m", context="fn", detail="device_get")

    def test_new_finding_fails(self):
        new, stale, accepted = B.compare([self.F], [])
        assert new == [self.F] and not stale and not accepted

    def test_accepted_finding_passes(self):
        entry = B.BaselineEntry(key=self.F.key, justification="known debt")
        new, stale, accepted = B.compare([self.F], [entry])
        assert not new and not stale and accepted == [self.F]

    def test_stale_entry_fails(self):
        entry = B.BaselineEntry(key="RPR999 gone::x::y", justification="old")
        new, stale, accepted = B.compare([], [entry])
        assert not new and stale == [entry]

    def test_key_is_line_independent(self):
        moved = Finding(code="RPR002", path=self.F.path, line=99,
                        message="m", context="fn", detail="device_get")
        assert moved.key == self.F.key

    def test_load_rejects_empty_justification(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps(
            {"version": 1, "entries": [{"key": "RPR001 a::b::c",
                                        "justification": "  "}]}))
        with pytest.raises(ValueError, match="no justification"):
            B.load(p)

    def test_write_preserves_justifications(self, tmp_path):
        p = tmp_path / "b.json"
        B.write(p, [self.F])
        data = json.loads(p.read_text())
        data["entries"][0]["justification"] = "because reasons"
        p.write_text(json.dumps(data))
        B.write(p, [self.F])
        assert B.load(p)[0].justification == "because reasons"

    def test_cli_stale_baseline_fails(self, tmp_path, capsys):
        from repro.analysis.__main__ import main

        p = tmp_path / "b.json"
        p.write_text(json.dumps({"version": 1, "entries": [
            {"key": "RPR001 src/repro/gone.py::fn::item()",
             "justification": "stale on purpose"}]}))
        rc = main(["--layers", "1", "--root", str(ROOT), "--baseline", str(p)])
        assert rc == 1
        assert "stale" in capsys.readouterr().out

    def test_cli_clean_layer1_passes(self, tmp_path, capsys):
        from repro.analysis.__main__ import main

        p = tmp_path / "b.json"
        p.write_text(json.dumps({"version": 1, "entries": []}))
        rc = main(["--layers", "1", "--root", str(ROOT), "--baseline", str(p)])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "new=0 stale=0" in out


# ------------------------------------------------------------ repo sweep
def test_layer1_sweep_is_clean_with_real_allowlist():
    """src/repro carries zero unallowlisted Layer-1 findings — the no-host-
    sync contract holds at the source level."""
    fs = R.check_tree(ROOT)
    assert fs == [], "\n".join(f.format() for f in fs)


def test_allowlist_entries_all_fire():
    """Every ALLOWLIST seam still exists: with the allowlist disabled, each
    key must show up in the sweep — a dead entry is a stale suppression."""
    fired = {f.key for f in R.check_tree(ROOT, allowlist={})}
    dead = [k for k in R.ALLOWLIST if k not in fired]
    assert not dead, f"allowlist entries no longer fire: {dead}"


def test_committed_baseline_loads_and_is_justified():
    entries = B.load(ROOT / B.BASELINE_NAME)
    assert all(e.justification for e in entries)


def test_orphan_report_is_quiet():
    """The import graph reaches every module from the entry-point roots
    (advisory, but pinned: a new orphan should be a conscious decision)."""
    from repro.analysis import imports as I

    assert I.orphans(ROOT) == []


def test_rule_catalog_matches_readme_badge():
    import re

    # importing the layers registers every rule
    assert len(RULE_CATALOG) == 12, sorted(RULE_CATALOG)
    readme = (ROOT / "README.md").read_text()
    m = re.search(r"analysis-(\d+)[_ ]rules", readme)
    assert m, "README.md must carry the analysis rule-count badge"
    assert int(m.group(1)) == len(RULE_CATALOG)
