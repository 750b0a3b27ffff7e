"""Fused multi-objective routing window as a Pallas TPU kernel.

The paper's gateway makes one Algorithm-1 decision per request with live
queue feedback — decision w+1 must see the queue bump of decision w, a
strictly sequential recurrence. Done naively (one jnp dispatch per request)
each step round-trips the queue vector through HBM; fused here, the profile
tables (P x G), the queue vector and the whole W-request scan live in VMEM
for a single kernel launch (TPU-native analogue of the paper's HAProxy+Lua
"microsecond-scale decision" requirement).

Layout: everything kept 2D with the pair axis last (lane dimension,
padded to a multiple of 128 by ops.py). Single program, grid=().
VMEM: 3 x (G x P') profile tables + (1 x P') queue + (W x 1) ids and
choices — a P'=1024, G=8, W=4096 window holds ~100 KiB of tables; the
(W x 1) int32 blocks take 2 MiB each if padded to 128 lanes. Rows are read
from the refs (``ref[pl.ds(g, 1), :]``) and each choice is stored as a
(1, 1) block: the forms Mosaic lowers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 1e30


def _first_argmin(J, lane):
    """Lane of the first minimum of the (1, P') row ``J``: ``jnp.argmin``'s
    tie rule, written out because the compiled kernel's ``jnp.argmin``
    does not keep it. On a v5e it picked another of two exactly tied
    pairs for over half of the paper fleet's requests."""
    return jnp.min(jnp.where(J == jnp.min(J), lane, J.shape[1]))


def _moscore_kernel(tg_ref, eg_ref, mg_ref, g_ref, q0_ref, out_ref, qf_ref,
                    *, delta: float, gamma: float, n_window: int):
    # tg/eg/mg: (G, P') profiles transposed; g: (W, 1) int32; q0: (1, P')
    _, p = tg_ref.shape

    def body(w, q):
        row = pl.ds(g_ref[w, 0], 1)
        Tg = tg_ref[row, :]                                        # (1, P')
        Eg = eg_ref[row, :]
        Mg = mg_ref[row, :]

        feasible = Mg >= jnp.max(Mg) - delta
        L = Tg * (1.0 + q)
        l_min = jnp.min(jnp.where(feasible, L, BIG))
        l_max = jnp.max(jnp.where(feasible, L, -BIG))
        e_min = jnp.min(jnp.where(feasible, Eg, BIG))
        e_max = jnp.max(jnp.where(feasible, Eg, -BIG))
        Ln = (L - l_min) / jnp.maximum(l_max - l_min, 1e-9)
        En = (Eg - e_min) / jnp.maximum(e_max - e_min, 1e-9)
        J = jnp.where(feasible, gamma * Ln + (1.0 - gamma) * En, BIG)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)
        sel = _first_argmin(J, lane)
        out_ref[pl.ds(w, 1), :] = jnp.full((1, 1), sel, jnp.int32)
        return q + (lane == sel).astype(q.dtype)

    q = jax.lax.fori_loop(0, n_window, body, q0_ref[...].astype(jnp.float32))
    qf_ref[...] = q.astype(qf_ref.dtype)


def _moscore_hoisted_kernel(tg_ref, en_ref, fs_ref, g_ref, q0_ref, out_ref,
                            qf_ref, *, gamma: float, n_window: int):
    # The invariant-hoisted variant: the accuracy-feasibility mask and the
    # normalised energy term are queue-independent, so ops.py precomputes
    # them once per table (core.policies.mo_precompute) and the kernel's
    # W-step loop keeps only the L = T_g*(1+q) normalisation + argmin —
    # 2 masked reductions and 1 divide per step instead of 5 and 2, and
    # one fewer (G, P') table in VMEM doing per-step reduction work.
    # tg/en: (G, P') f32; fs: (G, P') f32 {0, 1}; g: (W, 1) int32;
    # q0: (1, P'). Decisions are bit-identical to _moscore_kernel's (the
    # surviving per-step expression is written identically).
    _, p = tg_ref.shape

    def body(w, q):
        row = pl.ds(g_ref[w, 0], 1)
        Tg = tg_ref[row, :]                                        # (1, P')
        En = en_ref[row, :]
        feas = fs_ref[row, :] > 0.0

        L = Tg * (1.0 + q)
        l_min = jnp.min(jnp.where(feas, L, BIG))
        l_max = jnp.max(jnp.where(feas, L, -BIG))
        Ln = (L - l_min) / jnp.maximum(l_max - l_min, 1e-9)
        J = jnp.where(feas, gamma * Ln + (1.0 - gamma) * En, BIG)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)
        sel = _first_argmin(J, lane)
        out_ref[pl.ds(w, 1), :] = jnp.full((1, 1), sel, jnp.int32)
        return q + (lane == sel).astype(q.dtype)

    q = jax.lax.fori_loop(0, n_window, body, q0_ref[...].astype(jnp.float32))
    qf_ref[...] = q.astype(qf_ref.dtype)


def moscore_hoisted_pallas(Tt, Ent, Ft, gs, q0, *, gamma: float,
                           interpret: bool = True):
    """Invariant-hoisted kernel: Tt (G, P') fp32 transposed profile, Ent
    (G, P') the precomputed normalised-energy term, Ft (G, P') fp32
    feasibility mask (1.0 feasible / 0.0 not — padded pairs 0), gs (W, 1)
    int32, q0 (1, P') fp32. Returns (choices (W, 1) int32, q_final
    (1, P') fp32), bit-identical to :func:`moscore_pallas` on the same
    unquantized tables."""
    g_dim, p = Tt.shape
    w = gs.shape[0]
    kernel = functools.partial(_moscore_hoisted_kernel, gamma=gamma,
                               n_window=w)
    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[pl.BlockSpec(Tt.shape, lambda: (0, 0)),
                  pl.BlockSpec(Ent.shape, lambda: (0, 0)),
                  pl.BlockSpec(Ft.shape, lambda: (0, 0)),
                  pl.BlockSpec(gs.shape, lambda: (0, 0)),
                  pl.BlockSpec(q0.shape, lambda: (0, 0))],
        out_specs=[pl.BlockSpec((w, 1), lambda: (0, 0)),
                   pl.BlockSpec((1, p), lambda: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((w, 1), jnp.int32),
                   jax.ShapeDtypeStruct((1, p), jnp.float32)],
        interpret=interpret,
    )(Tt, Ent, Ft, gs, q0)


def moscore_pallas(Tt, Et, Mt, gs, q0, *, delta: float, gamma: float,
                   interpret: bool = True):
    """Tt/Et/Mt: (G, P') fp32 transposed profiles (P' multiple of 128);
    gs: (W, 1) int32; q0: (1, P') fp32. Returns (choices (W,1) int32,
    q_final (1, P') fp32)."""
    g_dim, p = Tt.shape
    w = gs.shape[0]
    kernel = functools.partial(_moscore_kernel, delta=delta, gamma=gamma,
                               n_window=w)
    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[pl.BlockSpec(Tt.shape, lambda: (0, 0)),
                  pl.BlockSpec(Et.shape, lambda: (0, 0)),
                  pl.BlockSpec(Mt.shape, lambda: (0, 0)),
                  pl.BlockSpec(gs.shape, lambda: (0, 0)),
                  pl.BlockSpec(q0.shape, lambda: (0, 0))],
        out_specs=[pl.BlockSpec((w, 1), lambda: (0, 0)),
                   pl.BlockSpec((1, p), lambda: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((w, 1), jnp.int32),
                   jax.ShapeDtypeStruct((1, p), jnp.float32)],
        interpret=interpret,
    )(Tt, Et, Mt, gs, q0)
