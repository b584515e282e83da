"""Property tests for the MoE capacity-dispatch tables (pure function —
the invariants any expert-parallel dispatch must satisfy)."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.configs.base import MoESpec
from repro.models.moe import dispatch_tables, route


@given(st.integers(min_value=1, max_value=64),   # tokens
       st.integers(min_value=1, max_value=4),    # top-k
       st.integers(min_value=2, max_value=16),   # experts
       st.integers(min_value=1, max_value=8),    # capacity
       st.integers(min_value=0, max_value=3))    # seed
@settings(max_examples=80, deadline=None)
def test_dispatch_tables_invariants(N, k, E, C, seed):
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, E, size=(N, k)).astype(np.int32))
    # local group = all experts (e0=0, n_local=E)
    table, slot = jax.jit(dispatch_tables, static_argnames=(
        "e0", "n_local", "capacity"))(idx, e0=0, n_local=E, capacity=C)
    table = np.asarray(table)
    slot = np.asarray(slot)
    assert table.shape == (E, C)

    # 1. every real entry points to a token that chose that expert
    flat = np.asarray(idx).reshape(-1)
    for e in range(E):
        for c in range(C):
            t = table[e, c]
            if t < N:
                s = slot[e, c]
                assert s >= 0
                assert s // k == t          # slot belongs to that token
                assert flat[s] == e         # and routed to this expert

    # 2. no (token, k-slot) is dispatched twice
    used = slot[slot >= 0]
    assert len(np.unique(used)) == len(used)

    # 3. capacity: expert e serves min(count_e, C) assignments, in order
    for e in range(E):
        count = int((flat == e).sum())
        served = int((table[e] < N).sum())
        assert served == min(count, C)
        # slots fill from the left
        real = table[e] < N
        assert not np.any(~real[:-1] & real[1:])


@given(st.integers(min_value=1, max_value=32),
       st.integers(min_value=2, max_value=64))
@settings(max_examples=60, deadline=None)
def test_ep_tp_factorization(tp, E):
    spec = MoESpec(num_experts=E, top_k=2, d_ff_expert=64)
    ep, tp_ff = spec.ep_tp(tp)
    assert ep * tp_ff == tp
    assert E % ep == 0


def test_route_gates_normalized():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    idx, gate = route(h, w, top_k=3)
    assert idx.shape == (32, 3) and gate.shape == (32, 3)
    np.testing.assert_allclose(np.asarray(gate).sum(-1), 1.0, rtol=1e-5)
    # top-k really is top-k
    logits = np.asarray(h) @ np.asarray(w)
    for i in range(32):
        want = set(np.argsort(logits[i])[-3:])
        assert set(np.asarray(idx)[i].tolist()) == want


@given(st.integers(min_value=1, max_value=24),   # received rows
       st.integers(min_value=1, max_value=4),    # top-k slots a row
       st.integers(min_value=1, max_value=6),    # experts held
       st.floats(min_value=0.0, max_value=1.0),  # share of slots held here
       st.integers(min_value=0, max_value=3))    # seed
@settings(max_examples=15, deadline=None)
def test_expert_pieces_sum_every_pair(R, k, n_loc, share, seed):
    """The train path's grouped products over every received slot, sorted
    by expert, give every (row, held expert) pair's gated product summed
    per row, from no pair held here to every slot; gradients as well,
    through the gathers' own transposes."""
    from repro.models.layers import activation
    from repro.models.moe import experts

    rng = np.random.default_rng(seed)
    d, ff = 8, 4
    ids = np.full((R, k), n_loc, np.int32)
    for r in range(R):
        held = rng.permutation(n_loc)[:k]
        take = rng.random(len(held)) < share
        ids[r, :len(held)] = np.where(take, held, n_loc)
    ids = jnp.asarray(ids.reshape(-1))
    gates = jnp.asarray(rng.random(R * k).astype(np.float32))
    rows = jnp.asarray(rng.standard_normal((R, d)).astype(np.float32))
    w_in = jnp.asarray(rng.standard_normal((n_loc, d, 2, ff))
                       .astype(np.float32))
    w_out = jnp.asarray(rng.standard_normal((n_loc, ff, d))
                        .astype(np.float32))

    def dense(rows, gates, w_in, w_out):
        e = jnp.clip(ids, 0, n_loc - 1)
        x = jnp.repeat(rows, k, axis=0)
        u = jnp.einsum("pd,pdgf->pgf", x, w_in[e],
                       precision=jax.lax.Precision.HIGHEST)
        a = activation("silu", u[:, 0], u[:, 1])
        out = jnp.einsum("pf,pfd->pd", a, w_out[e],
                         precision=jax.lax.Precision.HIGHEST)
        out = jnp.where((ids < n_loc)[:, None], out * gates[:, None], 0.0)
        return out.reshape(R, k, d).sum(axis=1)

    def grouped(rows, gates, w_in, w_out):
        return experts(rows, ids, gates, w_in, w_out, k=k, act="silu")

    # float32 on the CPU, full precision on both sides: what separates them
    # is the order of the sums (a row's pairs), rounding alone
    args = (rows, gates, w_in, w_out)
    np.testing.assert_allclose(jax.jit(grouped)(*args), dense(*args),
                               rtol=1e-5, atol=1e-5)
    ct = jnp.asarray(rng.standard_normal((R, d)).astype(np.float32))
    every = (0, 1, 2, 3)
    got = jax.grad(lambda *a: jnp.sum(grouped(*a) * ct), every)(*args)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * ct), every)(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
