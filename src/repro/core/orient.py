"""Skeleton → CPDAG: v-structure extraction + Meek rules (paper §2.4 step 2).

The paper accelerates only the skeleton phase ("the second step is fairly
fast") but a complete system needs the CPDAG, so we implement it — fully
vectorised in JAX so it runs sharded alongside the skeleton phase.

Representation: directed adjacency D (n,n) bool; an *undirected* edge is
D[i,j] = D[j,i] = True; a directed edge i→j is D[i,j]=True, D[j,i]=False.

Two steps need a third vertex index: the v-structure search and Meek rule
R3. Both walk each vertex's compacted neighbour list (core/compact.py) of
static width ``n_prime`` ≥ the skeleton's max degree, row by row:
O(n·n′²·Lmax) work and O(n′·n) memory per row, where dense (n,n,n,Lmax)
forms would ask 35 GB at the paper's n=1643. The host drivers pass their
bucketed max degree; ``n_prime=None`` means n, which is exact for any
skeleton (the batch scan and the service use it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .compact import compact_rows

#: vertices whose neighbour-list rows are processed together by lax.map
ROW_BATCH = 64


def sepset_membership(sep: jax.Array) -> jax.Array:
    """sep (n,n,Lmax) int32 id-lists → (n,n,n) bool, [i,j,k] = k ∈ SepSet(i,j).

    The padding sentinels (-1 / -2) never equal a variable id, so they read
    as "not a member". Used by the ensemble aggregate
    (repro/batch/ensemble.py), which majority-votes these membership
    tensors across bootstrap replicates.
    """
    n = sep.shape[0]
    ks = jnp.arange(n)
    return jnp.any(sep[:, :, None, :] == ks[None, None, :, None], axis=-1)


def _into_k_compact(adj: jax.Array, sep: jax.Array, n_prime: int) -> jax.Array:
    """(i, k) → some j completes an unshielded i—k—j with k ∉ SepSet(i, j),
    from each k's neighbour list (width n_prime ≥ max degree)."""
    n = adj.shape[0]
    nbrs, _ = compact_rows(adj, n_prime)

    def row(args):
        k, nb = args
        ok = nb >= 0
        ids = jnp.clip(nb, 0, n - 1)
        pair = (ok[:, None] & ok[None, :] & (ids[:, None] != ids[None, :])
                & ~adj[ids[:, None], ids[None, :]])
        in_sep = jnp.any(sep[ids[:, None], ids[None, :]] == k, axis=-1)
        hit = jnp.any(pair & ~in_sep, axis=1).astype(jnp.int32)
        return jnp.zeros(n, jnp.int32).at[ids].max(hit) > 0  # row k of into_k.T

    ks = jnp.arange(n, dtype=jnp.int32)
    return jax.lax.map(row, (ks, nbrs), batch_size=ROW_BATCH).T


def orient_v_structures(adj: jax.Array, sep: jax.Array,
                        n_prime: int | None = None) -> jax.Array:
    """For every unshielded triple i—k—j (i,j non-adjacent) with
    k ∉ SepSet(i,j): orient i→k←j.

    sep: (n,n,Lmax) int32 separating-set ids, -1 padded; sep[i,j] is valid
    only for removed edges (adj[i,j] == False there). n_prime: a static
    bound on adj's max degree (None: n).
    """
    adj = adj.astype(bool)
    n_prime = adj.shape[0] if n_prime is None else n_prime
    return _orient_into(adj, _into_k_compact(adj, sep, n_prime))


def orient_v_structures_membership(adj: jax.Array, in_sep: jax.Array) -> jax.Array:
    """v-structure orientation from a boolean membership tensor in_sep
    (n,n,n), [i,j,k] = k ∈ SepSet(i,j) — the form ensemble aggregation
    produces directly (no id-list tensor exists for a voted sepset)."""
    n = adj.shape[0]
    adj = adj.astype(bool)

    eye = jnp.eye(n, dtype=bool)
    nonadj = ~adj & ~eye  # i,j distinct non-adjacent
    triple = adj[:, None, :] & adj[None, :, :] & nonadj[:, :, None]  # i-k, j-k
    vstruct = triple & ~in_sep  # (i, j, k): orient i→k and j→k

    into_k = jnp.any(vstruct, axis=1)  # (i,k): some j completes a v at k
    return _orient_into(adj, into_k)


def _orient_into(adj: jax.Array, into_k: jax.Array) -> jax.Array:
    """Apply the v-structure arrows into_k[i, k] (orient i→k) to adj."""
    d = adj
    # i→k: keep D[i,k], drop D[k,i]
    drop = into_k.T & adj  # remove k→i direction
    # conflict resolution: if both i→k and k→i demanded (overlapping v-structs),
    # pcalg default (u.t. = not conservative) lets later overwrite; we drop both
    # directions' reverse, leaving a bidirected edge resolved to undirected.
    both = into_k & into_k.T
    d = d & ~(drop & ~both.T)
    d = jnp.where(both | both.T, adj, d)  # restore as undirected on conflict
    return d


def _r3_compact(und, dir_, nonadj, n_prime: int) -> jax.Array:
    """Meek R3's ∃ c, d ∈ und(a), c,d non-adjacent, c→b, d→b, from a's
    undirected-neighbour list (width n_prime ≥ max degree)."""
    n = und.shape[0]
    nbrs, _ = compact_rows(und, n_prime)

    def row(nb):
        ok = nb >= 0
        ids = jnp.clip(nb, 0, n - 1)
        pair = ok[:, None] & ok[None, :] & nonadj[ids[:, None], ids[None, :]]
        into_b = dir_[ids] & ok[:, None]  # (n′, n): c_p → b
        # 0/1 operands and counts ≤ n′ are exact at any matmul precision
        cnt = jnp.dot(pair.astype(jnp.float32), into_b.astype(jnp.float32))
        return jnp.any((cnt > 0) & into_b, axis=0)

    return jax.lax.map(row, nbrs, batch_size=ROW_BATCH)


def _meek_step(d: jax.Array, n_prime: int) -> jax.Array:
    """One parallel sweep of Meek rules R1–R4. Returns updated digraph."""
    und = d & d.T  # undirected edges
    dir_ = d & ~d.T  # directed edges a→b
    adj_any = d | d.T

    # R1: a→b, b—c, a,c non-adjacent  ⇒  b→c
    nonadj = ~adj_any & ~jnp.eye(d.shape[0], dtype=bool)
    r1 = jnp.einsum("ab,bc,ac->bc", dir_, und, nonadj) > 0

    # R2: a→b→c and a—c  ⇒  a→c
    r2 = (jnp.einsum("ab,bc->ac", dir_, dir_) > 0) & und

    # R3: a—b, a—c, a—d, c→b, d→b, c,d non-adjacent  ⇒  a→b
    r3 = _r3_compact(und, dir_, nonadj, n_prime) & und

    # R4: a—b, a—c (or a adj d), c→d? canonical: a—d, c→b? Use pcalg form:
    # a—b, a—d, c→b, d→c, a,c adjacent? (rule 4: a—b, c→b, d→c, a—d, a adj c)
    r4 = (jnp.einsum("ad,dc,cb,ac->ab", und, dir_, dir_, adj_any) > 0) & und

    orient = r1 | r2 | r3 | r4  # a→b decisions
    # apply: remove reverse direction of newly-oriented undirected edges,
    # unless both directions demanded (cycle-ambiguous) — keep undirected.
    conflict = orient & orient.T
    orient = orient & ~conflict
    return d & ~(orient.T)


def meek_rules(d: jax.Array, max_iter: int | None = None,
               n_prime: int | None = None) -> jax.Array:
    """Iterate Meek sweeps to fixpoint (≤ n² sweeps; usually a handful).
    n_prime: static max-degree bound, as in :func:`orient_v_structures`."""
    n = d.shape[0]
    iters = max_iter or (n * n)
    n_prime = n if n_prime is None else n_prime

    def cond(state):
        d_prev, d_cur, i = state
        return (i < iters) & jnp.any(d_prev != d_cur)

    def body(state):
        _, d_cur, i = state
        return d_cur, _meek_step(d_cur, n_prime), i + 1

    d0 = d
    d1 = _meek_step(d0, n_prime)
    _, d_final, _ = jax.lax.while_loop(cond, body, (d0, d1, jnp.int32(1)))
    return d_final


@functools.partial(jax.jit, static_argnames=("n_prime",))
def cpdag_from_skeleton(adj: jax.Array, sep: jax.Array,
                        n_prime: int | None = None) -> jax.Array:
    """Full step-2: v-structures then Meek closure → CPDAG digraph.
    n_prime: static bound on adj's max degree (host drivers pass their
    bucketed degree; None: n, exact for any skeleton)."""
    return meek_rules(orient_v_structures(adj, sep, n_prime), n_prime=n_prime)


def cpdag_from_membership(adj: jax.Array, in_sep: jax.Array) -> jax.Array:
    """Step-2 from a membership tensor (n,n,n) instead of id-lists — used by
    the bootstrap ensemble's aggregated skeleton + voted sepsets."""
    return meek_rules(orient_v_structures_membership(adj, in_sep))


# ---------------------------------------------------------------------------
# host oracles for tests
# ---------------------------------------------------------------------------
def cpdag_np(adj: np.ndarray, sepsets: dict) -> np.ndarray:
    """Serial reference CPDAG (mirrors pcalg udag2pdagRelaxed, rules 1-4)."""
    n = adj.shape[0]
    d = adj.copy().astype(bool)
    # v-structures
    for k in range(n):
        nb = np.flatnonzero(adj[k])
        for ii in range(len(nb)):
            for jj in range(ii + 1, len(nb)):
                i, j = int(nb[ii]), int(nb[jj])
                if adj[i, j]:
                    continue
                s = sepsets.get((min(i, j), max(i, j)), ())
                if k not in s:
                    d[k, i] = False
                    d[k, j] = False
    changed = True
    while changed:
        changed = False
        und = d & d.T
        dir_ = d & ~d.T
        adj_any = d | d.T
        for a in range(n):
            for b in range(n):
                if not und[a, b]:
                    continue
                # R1
                if any(dir_[x, a] and not adj_any[x, b] and x != b for x in range(n)):
                    d[b, a] = False
                    changed = True
                    continue
                # R2
                if any(dir_[a, x] and dir_[x, b] for x in range(n)):
                    d[b, a] = False
                    changed = True
                    continue
                # R3
                ok = False
                for c in range(n):
                    for e in range(n):
                        if c == e or adj_any[c, e]:
                            continue
                        if und[a, c] and und[a, e] and dir_[c, b] and dir_[e, b]:
                            ok = True
                if ok:
                    d[b, a] = False
                    changed = True
                    continue
                # R4
                for dd in range(n):
                    for c in range(n):
                        if und[a, dd] and dir_[dd, c] and dir_[c, b] and adj_any[a, c]:
                            d[b, a] = False
                            changed = True
                            break
    return d
