"""Unified sharding layer: row-sharded C, sharded batch axis, spec/memory
contracts. Multi-device cases run in subprocesses so the fake-device
XLA flag doesn't leak into other tests (same pattern as
test_distributed_pc.py); layout-parity unit tests run in-process."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.distributed


def _run_script(script, ndev=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout, r.stdout[-2000:]


# ------------------------------------------------------- in-process helpers
def test_padding_helpers_roundtrip():
    import jax.numpy as jnp

    from repro.core import sharding as SH

    mesh = SH.make_mesh(1)
    assert SH.mesh_size(mesh) == 1
    x = jnp.arange(7)
    padded, pad = SH.pad_leading(x, mesh)
    assert pad == 0 and padded.shape == (7,)
    np.testing.assert_array_equal(np.asarray(SH.unpad_leading(padded, pad)), np.arange(7))


def test_make_mesh_errors_actionably_on_too_many_devices():
    import jax

    from repro.core import sharding as SH

    want = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
        SH.make_mesh(want)


def test_gather_s_cols_bit_identical_to_dense_gather():
    """The row-sharded C layout (local rows + gathered candidate columns)
    feeds the CI sweep the exact fp32 values of the dense layout — checked
    directly on the gather prologues, no mesh required."""
    import jax.numpy as jnp

    from repro.core import levels as L
    from repro.core.cit import correlation_from_samples, threshold
    from repro.core.compact import compact_rows
    from repro.data.synthetic_dag import sample_gaussian_dag

    x, _ = sample_gaussian_dag(n=22, m=2000, density=0.15, seed=5)
    c = correlation_from_samples(jnp.asarray(x))
    n = 22
    adj = L.level0(c, threshold(2000, 0, 0.01))
    npr = int(jnp.max(jnp.sum(adj, axis=1)))
    compact, counts = compact_rows(adj, n_prime=npr)
    rows = jnp.arange(n, dtype=jnp.int32)
    ranks = jnp.arange(6, dtype=L._rank_dtype())

    counts_host = np.asarray(jnp.sum(adj, axis=1))
    cols = np.flatnonzero(counts_host > 0).astype(np.int32)
    col_pos = np.zeros(n, np.int32)
    col_pos[cols] = np.arange(len(cols), dtype=np.int32)
    c_cols = c[:, jnp.asarray(cols)]

    for ell in (1, 2):
        dense = L.gather_s(c, adj, compact, counts, rows, ranks, ell=ell, n_max=npr)
        sharded = L.gather_s_cols(
            c, c_cols, jnp.asarray(col_pos), adj, compact, counts, rows, ranks,
            ell=ell, n_max=npr,
        )
        # masked cells may legitimately read different junk; everything the
        # sweep can use must agree bit-for-bit
        mask_d, mask_s = np.asarray(dense[4]), np.asarray(sharded[4])
        np.testing.assert_array_equal(mask_d, mask_s)
        tau = threshold(2000, ell, 0.01)
        found_d = L.ci_sweep(*dense[:5], tau, ell=ell)
        found_s = L.ci_sweep(*sharded[:5], tau, ell=ell)
        np.testing.assert_array_equal(np.asarray(found_d), np.asarray(found_s))
        np.testing.assert_array_equal(np.asarray(dense[5]), np.asarray(sharded[5]))


# ------------------------------------------------- sharded C (row layout)
@pytest.mark.parametrize("ndev,n,dens,seed", [
    (8, 30, 0.2, 4),      # 30 % 8 != 0 → row-pad path
    (4, 24, 0.25, 1),     # even split
])
def test_shard_c_bit_identical_to_replicated_and_single(ndev, n, dens, seed):
    _run_script(f"""
        import jax, numpy as np
        assert len(jax.devices()) == {ndev}, jax.devices()
        from repro.data.synthetic_dag import sample_gaussian_dag
        from repro.core.pc import pc
        from repro.core.distributed import pc_distributed

        x, _ = sample_gaussian_dag(n={n}, m=2500, density={dens}, seed={seed})
        base = pc(x, engine="S")
        repl = pc_distributed(x=x)
        shc = pc_distributed(x=x, shard_c=True)
        for run in (repl, shc):
            assert np.array_equal(base.adj, run.adj), "skeleton mismatch"
            assert np.array_equal(base.sepsets, run.sepsets), "sepset mismatch"
            assert np.array_equal(base.cpdag, run.cpdag), "cpdag mismatch"
        assert all(st["shard_c"] for st in shc.level_stats)
        print("OK")
    """, ndev=ndev)


def test_shard_c_memory_layout_specs():
    """ISSUE-3 acceptance: per-device C memory in the sharded-C path is
    O(n·k + n²/n_dev), not O(n²) — asserted via the sharding specs: the
    persistent C is row-sharded in (n_pad/n_dev, n) blocks, and the chunk
    bodies gather only k < n candidate columns."""
    _run_script("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        assert len(jax.devices()) == 8
        from repro.core import sharding as SH
        from repro.core.distributed import pc_distributed, shard_correlation
        from repro.core.cit import correlation_from_samples
        from repro.data.synthetic_dag import sample_gaussian_dag

        n, ndev = 33, 8
        x, _ = sample_gaussian_dag(n=n, m=2000, density=0.05, seed=7)
        c = correlation_from_samples(jnp.asarray(x))
        mesh = SH.make_mesh(ndev)

        c_sh = shard_correlation(c, mesh)
        n_pad = n + SH.pad_amount(n, mesh)
        assert c_sh.shape == (n_pad, n)
        assert c_sh.sharding == NamedSharding(mesh, P(SH.AXIS))
        for shard in c_sh.addressable_shards:
            # the n²/n_dev block — this device's ONLY persistent copy of C
            assert shard.data.shape == (n_pad // ndev, n), shard.data.shape

        run = pc_distributed(x=x, mesh=mesh, shard_c=True)
        assert run.level_stats, "no levels ran"
        for st in run.level_stats:
            assert st["shard_c"]
            assert st["k_cols"] < n, (st["k_cols"], n)   # O(n·k) gather, k < n
            assert SH.AXIS in st["c_sharding"]
            # the C the run read: one disjoint row block per device
            blocks = st["c_row_blocks"]
            assert len({d for d, _, _ in blocks}) == ndev, blocks
            assert [(a, b) for _, a, b in sorted(blocks, key=lambda t: t[1])] == [
                (r, r + n_pad // ndev) for r in range(0, n_pad, n_pad // ndev)]
        print("OK")
    """)


# --------------------------------------------- sharded sepset / cache / pipeline
@pytest.mark.parametrize("ndev,n,dens,seed", [
    (8, 30, 0.2, 4),      # 30 % 8 != 0 → row-pad path
    (4, 24, 0.25, 1),     # even split
])
def test_shard_sep_cache_pipeline_bit_identical(ndev, n, dens, seed):
    """ISSUE-4 acceptance: sharded-sepset + hot-column-cached + pipelined
    pc_distributed is bit-identical (skeleton, sepsets, CPDAG) to the
    replicated path and the single-device "S" engine, including
    n % n_dev ≠ 0, for every flag combination."""
    _run_script(f"""
        import jax, numpy as np
        assert len(jax.devices()) == {ndev}, jax.devices()
        from repro.data.synthetic_dag import sample_gaussian_dag
        from repro.core.pc import pc
        from repro.core.distributed import pc_distributed

        x, _ = sample_gaussian_dag(n={n}, m=2500, density={dens}, seed={seed})
        base = pc(x, engine="S")
        combos = [
            dict(shard_sep=True),
            dict(shard_c=True, shard_sep=True),
            dict(shard_c=True, shard_sep=True, pipeline_depth=3),
            dict(shard_c=True, cache_cols=False, pipeline_depth=2),
            dict(shard_sep=True, pipeline_depth=4),
        ]
        for kw in combos:
            run = pc_distributed(x=x, **kw)
            assert np.array_equal(base.adj, run.adj), ("skeleton", kw)
            assert np.array_equal(base.sepsets, run.sepsets), ("sepsets", kw)
            assert np.array_equal(base.cpdag, run.cpdag), ("cpdag", kw)
            for st in run.level_stats:
                assert st["shard_sep"] == kw.get("shard_sep", False)
                assert st["pipeline_depth"] == kw.get("pipeline_depth", 1)
        print("OK")
    """, ndev=ndev)


def test_shard_sep_memory_layout_spec():
    """ISSUE-4 acceptance: with shard_sep the persistent sepset tensor is
    row-sharded in (n_pad/n_dev, n, depth) blocks — per-device sepset
    memory O(n²·depth / n_dev), not O(n²·depth) — asserted on the actual
    addressable shards mid-run; the adjacency symmetrization stays the sole
    replicated commit (adj remains a full (n, n) per-device bool)."""
    _run_script("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        assert len(jax.devices()) == 8
        from repro.core import sharding as SH
        from repro.core import levels as L
        from repro.core.distributed import run_level_sharded
        from repro.core.cit import correlation_from_samples, threshold
        from repro.data.synthetic_dag import sample_gaussian_dag

        n, ndev, depth, m = 33, 8, 8, 2500        # 33 % 8 != 0 → pad path
        x, _ = sample_gaussian_dag(n=n, m=m, density=0.2, seed=7)
        c = correlation_from_samples(jnp.asarray(x))
        mesh = SH.make_mesh(ndev)
        adj = L.level0(c, threshold(m, 0, 0.01))
        sep = jnp.full((n, n, depth), -1, jnp.int32)
        sep = sep.at[:, :, 0].set(jnp.where(adj, -1, -2))
        sep_sh, pad = SH.shard_rows(sep, mesh, fill=-1)
        n_pad = n + SH.pad_amount(n, mesh)
        assert SH.per_device_rows(n, mesh) == n_pad // ndev

        adj2, sep2, st = run_level_sharded(
            c, adj, sep_sh, 1, threshold(m, 1, 0.01), mesh, shard_sep=True)
        assert st["shard_sep"] and not st["skipped"]
        assert sep2.sharding.spec == P(SH.AXIS)
        for shard in sep2.addressable_shards:
            # the O(n²·depth / n_dev) block — this device's ONLY persistent
            # copy of the sepset tensor
            assert shard.data.shape == (n_pad // ndev, n, depth), shard.data.shape
        # parity of the single sharded-commit level vs the replicated commit
        adj_r, sep_r, _ = run_level_sharded(
            c, adj, sep, 1, threshold(m, 1, 0.01), mesh, shard_sep=False)
        assert np.array_equal(np.asarray(adj2), np.asarray(adj_r))
        assert np.array_equal(np.asarray(sep2)[:n], np.asarray(sep_r))
        print("OK")
    """)


def test_hot_column_cache_parity_and_gather_counts():
    """ISSUE-4 satellite: cached and uncached sharded-C runs produce
    identical skeletons/sepsets, and the per-level column-gather collective
    count strictly decreases under the cache (1 gather at the first level,
    0 — pure local subsetting — afterwards, vs one per chunk uncached)."""
    _run_script("""
        import jax, numpy as np
        assert len(jax.devices()) == 8
        from repro.data.synthetic_dag import sample_gaussian_dag
        from repro.core.distributed import pc_distributed

        x, _ = sample_gaussian_dag(n=33, m=2500, density=0.2, seed=7)
        # small cell budget → several chunks per level, so the uncached
        # per-chunk gather count is visibly larger than the cache's
        cached = pc_distributed(x=x, shard_c=True, cell_budget=2**9)
        uncached = pc_distributed(x=x, shard_c=True, cache_cols=False,
                                  cell_budget=2**9)
        assert np.array_equal(cached.adj, uncached.adj)
        assert np.array_equal(cached.sepsets, uncached.sepsets)
        assert np.array_equal(cached.cpdag, uncached.cpdag)

        assert len(cached.level_stats) >= 2, "need multiple levels"
        for i, (sc, su) in enumerate(zip(cached.level_stats,
                                         uncached.level_stats)):
            assert su["col_gathers"] == su["chunks"] >= 1
            # first level pays the one gather; later levels subset the cache
            assert sc["col_gathers"] == (1 if i == 0 else 0)
            assert sc["col_gathers"] < su["col_gathers"] or su["chunks"] == 1
            assert sc["col_gather_bytes"] <= su["col_gather_bytes"]
        total_c = sum(s["col_gathers"] for s in cached.level_stats)
        total_u = sum(s["col_gathers"] for s in uncached.level_stats)
        assert total_c == 1 < total_u, (total_c, total_u)
        print("OK")
    """)


def test_registry_counts_agree_with_sharded_level_stats():
    """Counter-drift guard (ISSUE-7), distributed seam: the metrics
    registry fed by obs.record_level_stats in run_level_sharded must agree
    with the per-level stats dicts — dispatches, chunks, col_gathers AND
    col_gather_bytes, for both the cached and uncached column paths."""
    _run_script("""
        import jax, numpy as np
        assert len(jax.devices()) == 8
        from repro import obs
        from repro.data.synthetic_dag import sample_gaussian_dag
        from repro.core.distributed import pc_distributed

        x, _ = sample_gaussian_dag(n=33, m=2500, density=0.2, seed=7)
        for kw in (dict(shard_c=True, cell_budget=2**9),
                   dict(shard_c=True, cache_cols=False, cell_budget=2**9),
                   dict(engine='S-grid')):
            with obs.scoped(enabled=True), obs.scoped_registry() as reg:
                run = pc_distributed(x=x, **kw)
                st = run.level_stats
                assert reg.total(obs.DISPATCHES, layout="sharded") == \\
                    sum(s["dispatches"] for s in st), kw
                assert reg.total(obs.CHUNKS, layout="sharded") == \\
                    sum(s.get("chunks", 0) for s in st), kw
                if kw.get("shard_c"):
                    assert reg.total(obs.COL_GATHERS) == \\
                        sum(s.get("col_gathers", 0) for s in st), kw
                    assert reg.total(obs.COL_GATHER_BYTES) == \\
                        sum(s.get("col_gather_bytes", 0) for s in st), kw
        print("OK")
    """)


# --------------------------------------------- grid-resident engine (S-grid)
@pytest.mark.parametrize("ndev,n,dens,seed,combos", [
    # 30 % 8 != 0 → row-pad path; layouts + speculation + pipelined args
    (8, 30, 0.2, 4, [
        "dict(engine='S-grid')",
        "dict(engine='S-grid', shard_c=True, shard_sep=True, speculate=True)",
        "dict(engine='S-grid', shard_sep=True, pipeline_depth=3)",
    ]),
    # even split; replicated-C speculation and sharded-C grid
    (4, 24, 0.25, 1, [
        "dict(engine='S-grid', speculate=True)",
        "dict(engine='S-grid', shard_c=True)",
    ]),
])
def test_grid_engine_sharded_bit_identical(ndev, n, dens, seed, combos):
    """ISSUE-5 acceptance, distributed: the grid-resident engine (one fused
    tests+commit shard_map per level — the pipelined deque collapses to a
    single sharded launch) is bit-identical to the single-device "S" engine
    across layout combos, n % n_dev ≠ 0, pipelined args (moot → reported
    depth 1) and speculative dispatch, with host dispatches per level
    reduced to 1 (the level-stats dispatch counter)."""
    _run_script(f"""
        import jax, numpy as np
        assert len(jax.devices()) == {ndev}, jax.devices()
        from repro.data.synthetic_dag import sample_gaussian_dag
        from repro.core.pc import pc
        from repro.core.distributed import pc_distributed

        x, _ = sample_gaussian_dag(n={n}, m=2500, density={dens}, seed={seed})
        base = pc(x, engine="S")
        for kw in [{", ".join(combos)}]:
            run = pc_distributed(x=x, **kw)
            assert np.array_equal(base.adj, run.adj), ("skeleton", kw)
            assert np.array_equal(base.sepsets, run.sepsets), ("sepsets", kw)
            assert np.array_equal(base.cpdag, run.cpdag), ("cpdag", kw)
            ran = [st for st in run.level_stats if not st["skipped"]]
            assert ran and all(st["engine"] == "S-grid" for st in ran)
            assert all(st["dispatches"] == 1 for st in ran), (
                [(st["level"], st["dispatches"]) for st in ran], kw)
            assert all(st["pipeline_depth"] == 1 for st in ran), kw
            if kw.get("speculate"):
                # every level after the first consumed its speculative chunk
                assert all(st.get("speculative", False) for st in ran[1:]), (
                    [(st["level"], st.get("speculative")) for st in ran], kw)
        print("OK")
    """, ndev=ndev)


def test_grid_engine_sharded_multi_launch_and_spec_mismatch():
    """Grid distributed with a launch budget too small for one level: several
    fused launches per level (commits in ascending rank order) must still be
    bit-identical, including under speculation — where the speculative first
    chunk was planned with a DIFFERENT (previous-bucket) chunk length and the
    level resumes from its rank offset."""
    _run_script("""
        import jax, numpy as np
        assert len(jax.devices()) == 8
        from repro.data.synthetic_dag import sample_gaussian_dag
        from repro.core.pc import pc
        from repro.core.distributed import pc_distributed

        x, _ = sample_gaussian_dag(n=26, m=2000, density=0.25, seed=9)
        base = pc(x, engine="S")
        for kw in [dict(), dict(speculate=True)]:
            run = pc_distributed(x=x, engine="S-grid", cell_budget=2**9, **kw)
            assert np.array_equal(base.adj, run.adj), kw
            assert np.array_equal(base.sepsets, run.sepsets), kw
            assert np.array_equal(base.cpdag, run.cpdag), kw
            assert any(st["chunks"] > 1 for st in run.level_stats
                       if not st["skipped"]), "budget did not force multi-launch"
        print("OK")
    """)


def test_run_level_pipelined_parity_single_device():
    """Single-device split tests/commit dispatch-ahead (levels.chunk_s_tests
    + chunk_s_commit): bit-identical to the fused sync path at any depth —
    the stale alive snapshot only over-claims already-removed edges and the
    chained commit discards those claims. In-process, no mesh needed."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cit import correlation_from_samples
    from repro.core.pc import pc_from_corr
    from repro.data.synthetic_dag import sample_gaussian_dag

    x, _ = sample_gaussian_dag(n=26, m=2000, density=0.25, seed=9)
    c = correlation_from_samples(jnp.asarray(x))
    sync = pc_from_corr(c, 2000, engine="S", cell_budget=2**10)
    assert any(st["chunks"] > 2 for st in sync.level_stats), "want multi-chunk"
    for depth in (2, 5):
        piped = pc_from_corr(c, 2000, engine="S", cell_budget=2**10,
                             pipeline_depth=depth)
        np.testing.assert_array_equal(sync.adj, piped.adj)
        np.testing.assert_array_equal(sync.sepsets, piped.sepsets)
        np.testing.assert_array_equal(sync.cpdag, piped.cpdag)
        assert all(st["pipeline_depth"] == depth for st in piped.level_stats
                   if not st["skipped"] and st["chunks"] > 0)


# ------------------------------------------------- sharded batch axis
def test_shard_batch_parity_including_indivisible_b():
    """ISSUE-3 acceptance: sharded-batch pc_scan_batch / scan_levels_batch /
    bootstrap_pc are bit-identical to single-device runs, including a B not
    divisible by the device count (identity-graph pad + trim path)."""
    _run_script("""
        import jax, numpy as np, jax.numpy as jnp
        assert len(jax.devices()) == 8
        from repro.core import sharding as SH
        from repro.core.engines import batch_run
        from repro.core.cit import correlation_from_samples
        from repro.data.synthetic_dag import sample_gaussian_dag
        from repro.batch.scan_pc import pc_scan_batch, scan_levels_batch
        from repro.batch.ensemble import bootstrap_pc

        m = 1500
        cs = jnp.stack([correlation_from_samples(jnp.asarray(
            sample_gaussian_dag(n=20, m=m, density=0.2, seed=s)[0]))
            for s in range(6)])                      # B=6 on 8 devices
        mesh = SH.make_mesh(8)

        ref = pc_scan_batch(cs, m, max_level=3)
        sh = pc_scan_batch(cs, m, max_level=3, mesh=mesh)
        for f in ref._fields:
            a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(sh, f))
            assert a.shape == b.shape and np.array_equal(a, b), f

        r_ref, sched_ref = scan_levels_batch(cs, m, max_level=3)
        r_sh, sched_sh = scan_levels_batch(cs, m, max_level=3, mesh=mesh)
        assert sched_ref == sched_sh
        for f in r_ref._fields:
            assert np.array_equal(np.asarray(getattr(r_ref, f)),
                                  np.asarray(getattr(r_sh, f))), f

        br = batch_run(cs, m, mesh=mesh, level_sync=True, max_level=3)
        assert np.array_equal(np.asarray(br[0].adj), np.asarray(r_ref.adj))

        x, _ = sample_gaussian_dag(n=14, m=1000, density=0.15, seed=2)
        e_ref = bootstrap_pc(x, n_boot=9, max_level=2, seed=0)   # 9 % 8 != 0
        e_sh = bootstrap_pc(x, n_boot=9, max_level=2, seed=0, mesh=mesh)
        np.testing.assert_array_equal(e_ref.edge_freq, e_sh.edge_freq)
        np.testing.assert_array_equal(e_ref.cpdag, e_sh.cpdag)
        np.testing.assert_array_equal(e_ref.replicate_adj, e_sh.replicate_adj)
        np.testing.assert_array_equal(e_ref.replicate_ok, e_sh.replicate_ok)
        print("OK")
    """)


def test_shard_batch_spec_places_b_over_devices():
    _run_script("""
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P
        assert len(jax.devices()) == 4
        from repro.core import sharding as SH

        mesh = SH.make_mesh(4)
        cs = np.zeros((6, 10, 10), np.float32)       # B=6 → pad to 8
        sh, pad = SH.shard_batch(cs, mesh)
        assert pad == 2 and sh.shape == (8, 10, 10)
        assert sh.sharding.spec == P(SH.AXIS, None, None)
        for shard in sh.addressable_shards:
            assert shard.data.shape == (2, 10, 10)   # B_pad/n_dev local graphs
        print("OK")
    """, ndev=4)
