"""Pallas TPU kernel: stratified inverse-CDF priority sampling.

The driver mandates on-device priority sampling via Pallas (BASELINE.json:5).
The XLA path (replay/prioritized_device.py) materializes a [T*B] cumsum in
HBM and runs ``searchsorted`` — a log-depth gather chain that is latency-
bound on TPU. This kernel keeps the whole priority plane resident in VMEM
and replaces cumsum+search with TPU-native compute:

  * all prefix sums are TRIANGULAR-MATRIX MATMULS on the MXU (Mosaic has no
    cumsum primitive): within-chunk row CDFs are ``rs @ L``, chunk offsets
    are an exclusive prefix over per-chunk masses, in-row lane CDFs are
    ``rows @ L_B``;
  * each sample's ring row comes from chunked compare-and-count — [S, C]
    VPU tiles against all S stratified targets at once, instead of S
    binary searches;
  * the selected rows are gathered with a one-hot [S, C] x [C, B] MXU
    matmul — no dynamic indexing, no scalar loops.

The only loops are ``fori_loop``s over row chunks, so occupancy does not
depend on S or the priority distribution. VMEM budget: the plane (4 bytes
per slot; a 1M-transition per-device shard is 4 MB) plus O(S*C + C*C)
scratch.

Validity masking and the alpha exponent are applied by the caller (cheap
elementwise XLA ops; this keeps ring-position arithmetic out of the
kernel); zero-mass rows (invalid/padded) are never selected.

The kernel's per-draw cost is nearly flat in shard size (VMEM-resident,
chunked MXU phases) while XLA's HBM cumsum scales with it, so the kernel
is meant for large shards; ``benchmarks/sampler_bench.py --amortize``
is the comparison (not measured on the current installation). On the
chip both paths pick a float64 reference's cell for all but a fraction
of a percent of draws (chip_smoke.py, leg kernel).
``ReplayConfig.pallas_sampler`` stays opt-in per config.

The samplers take a plane as its flat cells, slot ``t`` of lane ``b`` at
cell ``t * num_envs + b`` — how the device rings store theirs
(replay/device.py: ``[1_000_000]`` for the apex preset's 62500 slots of 16
lanes) — plus ``num_envs``, and hand back (t, b). The draw is an inverse
CDF over that flat order, so any ``[R, L]`` view of the same cells selects
the same cell, and the kernel sees every plane through ONE shape family:
``pallas_stratified_sample`` views the cells as ``[ceil(T*B / 512), 512]``
(as ``[62500, 16]`` Mosaic would pad the minor dimension to 128 lanes: 8x
the bytes in VMEM, every MXU tile 7/8 empty). For a 512-wide plane
(replay/host.py) the view is the plane itself; a zero-mass tail is padded
only when ``T*B`` is not a multiple of 512.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray

_CHUNK = 512  # rows per chunk ([S, _CHUNK] compare tiles, [C, C] triangulars)
_LANES = 512  # width of the [rows, _LANES] view the kernel sees
KERNEL_NAME = "per_stratified_sample"  # findable in HLO text and traces


def _tri(n: int, strict: bool) -> Array:
    """[n, n] lower-triangular ones: L[i, j] = 1 if i < j (strict) or
    i <= j, so ``row_vector @ L`` is an exclusive/inclusive prefix sum
    along lanes."""
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return ((i < j) if strict else (i <= j)).astype(jnp.float32)


def _sample_kernel(w_ref, u_ref, t_out, b_out, p_out, tot_out, cdf_ref, *,
                   num_chunks: int, real_T: int):
    T, B = w_ref.shape
    S = u_ref.shape[0]
    C = T // num_chunks
    ones_b = jnp.ones((1, B), jnp.float32)
    tri_inc_c = _tri(C, strict=False)

    # Phase 1: per-chunk row masses (ones @ w contraction) and their
    # within-chunk inclusive prefix sums, stashed in scratch so the count
    # pass never re-reads the [T, B] plane. The total mass is the sum of
    # the chunks' last prefix entries.
    def mass_body(c, tot):
        w_c = w_ref[pl.ds(c * C, C), :]                   # [C, B]
        rs = jax.lax.dot_general(
            ones_b, w_c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)          # [1, C]
        cdf_c = jnp.dot(rs, tri_inc_c,
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
        cdf_ref[pl.ds(c, 1), :] = cdf_c
        return tot + cdf_c[:, C - 1:C]

    total = jax.lax.fori_loop(0, num_chunks, mass_body,
                              jnp.zeros((1, 1), jnp.float32))
    tot_out[:] = total
    # No margin: `total` IS the last entry of the CDF the count pass
    # compares against (the same stored prefix sums, added in the same
    # order), so a target u * total with u <= 1 never exceeds it and the
    # strict compare stops at or before the last row that added mass —
    # the top stratum cannot walk onto zero-mass padding.
    targets = u_ref[:] * total                            # [S, 1]

    # Phase 2: per-sample row index = #(row_cdf < target) and the CDF mass
    # strictly before that row (masked max). The chunk CDF offset rides the
    # loop carry (chunks are visited in order), so no cross-chunk prefix
    # array is ever materialized.
    def count_body(c, carry):
        counts, prev, off = carry
        cdf_c = cdf_ref[pl.ds(c, 1), :]                   # [1, C]
        cdf_row = off + cdf_c
        less = (cdf_row < targets).astype(jnp.float32)    # [S, C]
        counts = counts + jnp.sum(less, axis=1, keepdims=True)
        prev = jnp.maximum(prev, jnp.max(cdf_row * less, axis=1,
                                         keepdims=True))
        off = off + cdf_c[:, C - 1:C]
        return counts, prev, off

    counts0 = jnp.zeros((S, 1), jnp.float32)
    counts, prev_cdf, _ = jax.lax.fori_loop(
        0, num_chunks, count_body,
        (counts0, counts0, jnp.zeros((1, 1), jnp.float32)))
    # Clamp into the REAL (unpadded) rows: padded rows carry zero mass.
    t_idx = jnp.minimum(counts, float(real_T - 1)).astype(jnp.int32)
    t_out[:] = t_idx

    # Phase 3: gather the S selected rows with a one-hot MXU matmul.
    def gather_body(c, rows):
        iota = jax.lax.broadcasted_iota(jnp.int32, (S, C), 1) + c * C
        onehot = (iota == t_idx).astype(jnp.float32)      # [S, C]
        w_c = w_ref[pl.ds(c * C, C), :]                   # [C, B]
        return rows + jnp.dot(onehot, w_c,
                              preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

    rows = jax.lax.fori_loop(0, num_chunks, gather_body,
                             jnp.zeros((S, B), jnp.float32))

    # In-row lane pick: lane CDF via triangular matmul, compare-and-count.
    # The residual is clamped strictly inside the row's own mass so the
    # count always stops at a nonzero lane (the plateau-start argument:
    # the first lane whose cumulative mass reaches the residual must have
    # added mass), immune to cross-phase fp reduction-order differences.
    row_cum = jnp.dot(rows, _tri(B, strict=False),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)  # [S, B]
    row_total = row_cum[:, B - 1:B]                       # [S, 1]
    residual = jnp.minimum(targets - prev_cdf,
                           row_total * (1.0 - 1e-6))      # [S, 1]
    b_counts = jnp.sum((row_cum < residual).astype(jnp.int32), axis=1,
                       keepdims=True)
    b_idx = jnp.minimum(b_counts, B - 1)                  # [S, 1]
    b_out[:] = b_idx
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (S, B), 1)
    p_out[:] = jnp.sum(jnp.where(b_iota == b_idx, rows, 0.0), axis=1,
                       keepdims=True)


def stratified_sample(w: Array, rng: Array, batch_size: int, num_envs: int,
                      use_pallas: bool = False, interpret: bool = False
                      ) -> Tuple[Array, Array, Array, Array]:
    """Stratified inverse-CDF draw from the flat cells ``w`` [T * B] of a
    mass plane of ``num_envs`` lanes — the ONE implementation both replay
    samplers (transition and sequence) share.

    Returns (t_idx [S], b_idx [S], mass_sel [S], total []). Routing:
    ``use_pallas`` runs the VMEM kernel below; otherwise the portable XLA
    cumsum+searchsorted path.
    """
    u01 = (jnp.arange(batch_size, dtype=jnp.float32)
           + jax.random.uniform(rng, (batch_size,))) / batch_size
    return stratified_sample_at(w, u01, num_envs, use_pallas=use_pallas,
                                interpret=interpret)


def stratified_sample_at(w: Array, u: Array, num_envs: int,
                         use_pallas: bool = False, interpret: bool = False
                         ) -> Tuple[Array, Array, Array, Array]:
    """Inverse-CDF draw from a mass plane's flat cells at EXPLICIT uniforms
    ``u`` [S] in [0, 1) — the per-shard leg of a cross-shard stratified
    draw (replay/sharded.py): the coordinator lays ONE global ladder
    over the concatenated per-shard totals and hands each shard its
    local positions as fractions of its own mass, so draws land here in
    proportion to this plane's mass with exactly the single-plane P(i).
    Same (t_idx, b_idx, mass_sel, total) contract and Pallas/XLA
    routing as :func:`stratified_sample`.
    """
    if use_pallas:
        return pallas_stratified_sample(w, u, num_envs, interpret=interpret)
    cdf = jnp.cumsum(w)
    total = cdf[-1]
    idx = jnp.clip(jnp.searchsorted(cdf, u * total), 0, w.shape[0] - 1)
    t_idx = (idx // num_envs).astype(jnp.int32)
    b_idx = (idx % num_envs).astype(jnp.int32)
    return t_idx, b_idx, w[idx], total


SAMPLE_BLOCK = 32  # lanes per second-level block of the hierarchical draw


def stratified_sample_rows(w: Array, blk_sums: Array, u: Array
                           ) -> Tuple[Array, Array, Array, Array]:
    """Three-level XLA inverse-CDF draw at explicit uniforms ``u`` [S]:
    row pick by searchsorted over the [T] row-sum CDF (row sums reduced
    from ``blk_sums`` — a [T, NB] pass, not a plane pass), then block
    pick over the selected rows' [NB] block sums, then lane pick inside
    one ``SAMPLE_BLOCK``-wide sub-block — O(T + S*(NB + BLOCK)) work
    and O(S*(NB + BLOCK)) memory traffic against the flat path's O(T*B)
    cumsum, which is what lets the device priority planes beat the host
    sum-tree on aggregate draws/sec even on CPU
    (benchmarks/sampler_bench.py ``sharded`` arm).

    ``blk_sums`` [T, B // SAMPLE_BLOCK] must track the per-block
    partial sums of ``w``; the device sampler maintains it
    incrementally inside its write-back scatter (touched blocks only),
    so no draw ever re-reduces the plane. Each level's residual is
    clamped strictly inside the level's own mass (the kernel's
    plateau-start argument) — the levels reduce in different fp orders,
    so without the clamps a top-of-row target could walk one cell past
    the last written one. Same (t_idx, b_idx, mass_sel, total) contract
    as :func:`stratified_sample_at`.
    """
    T, B = w.shape
    NB = blk_sums.shape[1]
    BS = B // NB
    row_sums = blk_sums.sum(axis=1)
    cdf = jnp.cumsum(row_sums)
    total = cdf[-1]
    pos = u.astype(jnp.float32) * total
    t_idx = jnp.clip(jnp.searchsorted(cdf, pos), 0, T - 1)
    blk = blk_sums[t_idx]                                 # [S, NB]
    blk_cdf = jnp.cumsum(blk, axis=1)
    res = jnp.minimum(pos - (cdf[t_idx] - row_sums[t_idx]),
                      blk_cdf[:, -1] * (1.0 - 1e-6))[:, None]
    jb = jnp.minimum(
        jnp.sum((blk_cdf < res).astype(jnp.int32), axis=1, keepdims=True),
        NB - 1)                                           # [S, 1]
    res2 = res - (jnp.take_along_axis(blk_cdf, jb, axis=1)
                  - jnp.take_along_axis(blk, jb, axis=1))
    sub = w.reshape(T, NB, BS)[t_idx, jb[:, 0]]           # [S, BS]
    sub_cdf = jnp.cumsum(sub, axis=1)
    res2 = jnp.minimum(res2, sub_cdf[:, -1:] * (1.0 - 1e-6))
    b2 = jnp.minimum(
        jnp.sum((sub_cdf < res2).astype(jnp.int32), axis=1, keepdims=True),
        BS - 1)                                           # [S, 1]
    mass = jnp.take_along_axis(sub, b2, axis=1)[:, 0]
    b_idx = jb[:, 0] * BS + b2[:, 0]
    return t_idx.astype(jnp.int32), b_idx.astype(jnp.int32), mass, total


def importance_weights(mass_sel: Array, total: Array, n_valid: Array,
                       beta: Array) -> Array:
    """(N * P(i))^-beta, batch-max normalized; zero-mass selections
    (possible only through fp boundary pathology) get weight 0 instead of
    an enormous one that would crush the batch."""
    p_sel = jnp.maximum(mass_sel, 1e-12) / jnp.maximum(total, 1e-12)
    weights = (jnp.maximum(n_valid, 1.0) * p_sel) ** (-beta)
    weights = jnp.where(mass_sel > 0.0, weights, 0.0)
    return weights / jnp.maximum(jnp.max(weights), 1e-12)


@functools.partial(jax.jit, static_argnames=("num_envs", "interpret"))
def pallas_stratified_sample(w: Array, u: Array, num_envs: int,
                             interpret: bool = False
                             ) -> Tuple[Array, Array, Array, Array]:
    """Draw samples ~ w (the flat [T * B] cells of a non-negative mass
    plane of ``num_envs`` lanes) at stratified uniforms ``u`` [S] in [0, 1).

    Returns (t_idx [S], b_idx [S], p_sel [S], total []): ring rows, env
    lanes, the selected masses (for importance weights) and the total mass.
    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU
    tests); on a TPU backend it raises — the chip compiles the kernel
    with Mosaic or does not run it.
    """
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "the Pallas sampler is never interpreted on a TPU backend")
    S = u.shape[0]
    cells = w.shape[0]
    rows = -(-cells // _LANES)
    # Rows padded to a chunk multiple; zero-mass padding is never selected.
    rows_pad = -(-rows // _CHUNK) * _CHUNK
    dense = jnp.pad(w, (0, rows_pad * _LANES - cells))
    num_chunks = rows_pad // _CHUNK
    # Scoped VMEM stated from the shape: Mosaic's 16 MiB default is under
    # the kernel's need from S >= 768 draws on a 1M-cell plane. Compiled
    # for v5e over 512..8192 rows x 64..4096 draws, the compiler asked
    # for at most the plane + 11 [S, _CHUNK] f32 tiles + 1.5 MiB.
    vmem_limit = 4 * (rows_pad * _LANES + 12 * S * _CHUNK) + (4 << 20)

    r_idx, l_idx, p_sel, total = pl.pallas_call(
        functools.partial(_sample_kernel, num_chunks=num_chunks,
                          real_T=rows),
        out_shape=(
            jax.ShapeDtypeStruct((S, 1), jnp.int32),
            jax.ShapeDtypeStruct((S, 1), jnp.int32),
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((num_chunks, _CHUNK), jnp.float32),  # chunk CDFs
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        name=KERNEL_NAME,
        interpret=interpret,
    )(dense.reshape(rows_pad, _LANES), u.reshape((S, 1)))
    flat = jnp.minimum(r_idx[:, 0] * _LANES + l_idx[:, 0], cells - 1)
    return flat // num_envs, flat % num_envs, p_sel[:, 0], total[0, 0]
