"""Fused hypothesis unit (paper §3.5) as ONE Pallas kernel.

ASRPU's hypothesis unit is a single hardware block that merges duplicate
hypotheses (same prefix hash), applies the beam threshold, and sort-
selects the surviving top-K.  This kernel fuses merge + threshold + top-k
into one pallas_call whose grid walks blocks of up to 8 stream slots (one
slot per sublane), so the whole per-frame selection runs in one
VMEM-resident pass.

Division of labour: the hash ORDERING itself (the hardware sort unit's
first half) stays outside as one batched XLA argsort — sorting is the
one primitive Mosaic has no native story for — and the kernel consumes
the sorted rows.  Everything inside is lane rotations, selects and
row reductions, which Mosaic lowers natively:

  * segment heads compare each key with its rotated neighbour;
  * each live segment's channel max and exp-sum are folded into its
    head member by member, in row order (a loop over the longest live
    segment, usually a handful of members) — the same order in which
    `ref.hypothesis_unit`'s segment-sum accumulates, which is what keeps
    interpret-mode parity bit-for-bit (tests/test_hypothesis_unit.py);
  * top-k is k rounds of row max + lowest-index tie break.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

ROWS = 8                        # slots per grid step: one f32 sublane tile
_DEAD = -1                      # ref.HASH_SENTINEL as int32


def _merge_select(key, pb, pnb, *, k, beam):
    """Merge + threshold + top-k over (R, N) rows pre-sorted by key.

    key: int32 (the uint32 sort key bitcast; dead candidates carry
    _DEAD); pb/pnb: f32 CTC channels in the same order.  Returns
    (pos, pb, pnb, valid), each (R, k): `pos` indexes the SORTED row,
    pb/pnb are the merged channels of the selected representative, and
    `valid` (int32 0/1) applies the beam threshold."""
    R, n = key.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, n), 1)

    def ahead(x):                          # ahead(x)[i] = x[i + 1] (wraps)
        return pltpu.roll(x, n - 1, 1)

    head = (lane == 0) | (key != pltpu.roll(key, 1, 1))
    rep = head & (key != _DEAD)            # one representative per live hash

    def fold(update, init):
        """acc at each rep <- update(acc, member) for its segment's later
        members in row order; ks/xb/xn hold key/pb/pnb j places ahead."""
        def cont(j, ks):
            return rep & (ks == key) & (lane < n - j)

        def more(c):
            j, ks = c[0], c[1]
            return (j < n) & (jnp.max(jnp.where(cont(j, ks), 1.0, 0.0)) > 0)

        def step(c):
            j, ks, xb, xn, acc = c
            acc = update(acc, cont(j, ks), xb, xn)
            return j + 1, ahead(ks), ahead(xb), ahead(xn), acc

        return jax.lax.while_loop(
            more, step, (1, ahead(key), ahead(pb), ahead(pnb), init))[-1]

    mb, mn = fold(lambda a, c, xb, xn: (jnp.where(c, jnp.maximum(a[0], xb),
                                                  a[0]),
                                        jnp.where(c, jnp.maximum(a[1], xn),
                                                  a[1])),
                  (pb, pnb))
    sb, sn = fold(lambda a, c, xb, xn: (jnp.where(c, a[0] + jnp.exp(xb - mb),
                                                  a[0]),
                                        jnp.where(c, a[1] + jnp.exp(xn - mn),
                                                  a[1])),
                  (jnp.exp(pb - mb), jnp.exp(pnb - mn)))

    def lse(m, s):                         # = ref._seg_lse at the head
        out = m + jnp.log(s)
        return jnp.where(out > ref.NEG_INF / 2, out, ref.NEG_INF)

    pb_m, pnb_m = lse(mb, sb), lse(mn, sn)
    tot = jnp.where(rep, jnp.logaddexp(pb_m, pnb_m), ref.NEG_INF)
    best = jnp.max(tot, axis=1, keepdims=True)
    slot = jax.lax.broadcasted_iota(jnp.int32, (R, k), 1)

    def pick(i, c):                        # ties -> lowest index (top_k's)
        t, pos, top, opb, opnb = c
        mx = jnp.max(t, axis=1, keepdims=True)
        j = jnp.min(jnp.where(t == mx, lane, n), axis=1, keepdims=True)
        hit = lane == j
        at = slot == i

        def read(x):
            return jnp.max(jnp.where(hit, x, -jnp.inf), axis=1,
                           keepdims=True)

        return (jnp.where(hit, -jnp.inf, t), jnp.where(at, j, pos),
                jnp.where(at, mx, top), jnp.where(at, read(pb_m), opb),
                jnp.where(at, read(pnb_m), opnb))

    zf = jnp.zeros((R, k), jnp.float32)
    _, pos, top, opb, opnb = jax.lax.fori_loop(
        0, k, pick, (tot, jnp.zeros((R, k), jnp.int32), zf, zf, zf))
    valid = (top > ref.NEG_INF / 2) & (top >= best - beam)
    return (pos, jnp.where(valid, opb, ref.NEG_INF),
            jnp.where(valid, opnb, ref.NEG_INF), valid.astype(jnp.int32))


def _kernel(key_ref, pb_ref, pnb_ref, pos_ref, opb_ref, opnb_ref, oval_ref,
            *, k, beam):
    pos_ref[...], opb_ref[...], opnb_ref[...], oval_ref[...] = _merge_select(
        key_ref[...], pb_ref[...], pnb_ref[...], k=k, beam=beam)


def _merge_select_call(key_s, pb_s, pnb_s, *, k, beam, interpret):
    """key_s: (B, N) int32 sorted keys (dead = -1); pb_s/pnb_s: (B, N)
    f32 sorted channels.  N is a multiple of 128 and B is at most ROWS
    or a multiple of it.  Returns (pos, pb, pnb, valid) each (B, k);
    `pos` indexes the sorted row."""
    B, N = key_s.shape
    rows = min(B, ROWS)
    assert B % rows == 0 and N % 128 == 0, (B, N)
    block = lambda b: (b, 0)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, beam=float(beam)),
        grid=(B // rows,),
        in_specs=[pl.BlockSpec((rows, N), block)] * 3,
        out_specs=[pl.BlockSpec((rows, k), block)] * 4,
        out_shape=(jax.ShapeDtypeStruct((B, k), jnp.int32),
                   jax.ShapeDtypeStruct((B, k), jnp.float32),
                   jax.ShapeDtypeStruct((B, k), jnp.float32),
                   jax.ShapeDtypeStruct((B, k), jnp.int32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="hypothesis_unit",
    )(key_s, pb_s, pnb_s)


@functools.partial(jax.jit, static_argnames=("k", "beam", "interpret"))
def hypothesis_unit_pallas(hashes, pb, pnb, *, k, beam, interpret=False):
    """`ref.hypothesis_unit`'s contract through the kernel: hashes (B, N)
    int32, pb/pnb (B, N) f32 -> dict of (B, k) `idx`, `pb`, `pnb`,
    `valid`."""
    B, N = hashes.shape
    valid = jnp.logaddexp(pb, pnb) > ref.NEG_INF / 2
    key = jnp.where(valid, hashes.astype(jnp.uint32), ref.HASH_SENTINEL)
    # lane-align the rows and fill whole 8-slot blocks for Mosaic; pads
    # are dead candidates (and dead rows), dropped again below
    pad = ((0, (-B) % ROWS if B > ROWS else 0), (0, (-N) % 128))
    key = jnp.pad(key, pad, constant_values=ref.HASH_SENTINEL)
    pb = jnp.pad(pb, pad, constant_values=ref.NEG_INF)
    pnb = jnp.pad(pnb, pad, constant_values=ref.NEG_INF)
    # the hardware sort unit's ordering half: ONE batched XLA argsort;
    # dead candidates carry an out-of-range uint32 sentinel, so a live
    # hash equal to 2**31 - 1 can never be mistaken for one (the lane
    # padding is merge-neutral: pads sort into the sentinel tail and
    # contribute exact-zero mass, pinned bitwise by the parity tests
    # against the unpadded ref pipeline)
    order = jnp.argsort(key, axis=-1, stable=True)
    key_s = jax.lax.bitcast_convert_type(
        jnp.take_along_axis(key, order, axis=-1), jnp.int32)
    pb_s = jnp.take_along_axis(pb, order, axis=-1)
    pnb_s = jnp.take_along_axis(pnb, order, axis=-1)
    pos, opb, opnb, oval = (a[:B] for a in _merge_select_call(
        key_s, pb_s, pnb_s, k=k, beam=beam, interpret=interpret))
    order = order[:B]
    valid = oval.astype(bool)
    # order[pos] is the sorted segment head = the selected hash's FIRST
    # occurrence in the original row (stable sort), matching the
    # sort-free ref path; pruned slots pin to 0 in both paths
    idx = jnp.where(valid, jnp.take_along_axis(order, pos, axis=-1), 0)
    return {"idx": idx, "pb": opb, "pnb": opnb, "valid": valid}
