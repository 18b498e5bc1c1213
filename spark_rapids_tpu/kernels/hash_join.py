"""Fused build+probe hash join — match ranges over ONE hash limb.

The jnp reference (exec.join._match_ranges) stably sorts the build
side by the full (exclusion-flag + key-limbs) encoding and runs TWO
lexicographic bisections (lower + upper bound) over all of those limbs
per probe row.  The fused kernel collapses both costs:

* build: sort ONE uint64 limb — the 63-bit key hash with the exclusion
  flag in the top bit, so excluded (dead/null) rows sort after every
  probe value and can never be landed on; the key limbs and the flag
  ride that sort as payload operands;
* probe: NO gather.  The probe rows' hashes are sorted INTO the build
  side's order (hash_layout.merge_sorted), their key limbs along: a
  row's rank among the build slots is its lower bound, the build slots
  between it and the end of its hash run are its matches (the upper
  bound, from one running minimum), and a second sort on the first
  one's permutation brings both back to probe order — where a
  bisection took 19 dependent takes of 262 144 indices, 113 ms of the
  chip's time against 2.4 (docs/kernels.md);
* exactness: the probed run start's FULL key limbs come to the probe
  row in the merged order (hash_layout.fill_next) and are compared
  against it (a hash-only miss yields m = 0, never a wrong match), and
  a build-side adjacent-pair scan detects the one
  case that can't be repaired locally — two distinct live keys sharing
  a 64-bit hash — surfacing ``ok = False`` for the dispatcher's exact
  fallback (see hash_layout.hash_group_layout's argument for why
  adjacency detection is complete).

Bit-identity: within one hash run the stable sort keeps build rows in
original-index order — the same order the reference's key-sorted perm
gives inside a key group — so (m, lo, perm) drive exec.join._merge_join
to byte-identical materialized output.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.kernels import hash_layout as HL

# numpy scalar: module import stays safe before jax_enable_x64 flips on
_TOP = np.uint64(1 << 63)


def match_fused(l_limbs: List[jnp.ndarray], r_limbs: List[jnp.ndarray],
                r_excl: jnp.ndarray, use_pallas: bool = False
                ) -> Optional[Tuple[jnp.ndarray, jnp.ndarray,
                                    jnp.ndarray, jnp.ndarray]]:
    """(m, lo, perm, ok) under exec.join._match_ranges' contract, or
    None when the key limbs are unhashable (raw-f64 limb — the caller
    stays on the exact reference; static per kernel instance).

    ``l_limbs``/``r_limbs`` are the fused key limbs WITHOUT the
    exclusion flag (it rides the hash limb's top bit here); left-side
    liveness masking stays with the caller, as in the reference.
    """
    if not HL.limbs_hashable(l_limbs + r_limbs):
        return None
    n, q = int(r_excl.shape[0]), int(l_limbs[0].shape[0])
    h_r = HL.hash_limbs(r_limbs, use_pallas=use_pallas) >> jnp.uint64(1)
    build_limb = jnp.where(r_excl, h_r | _TOP, h_r)
    # build: the key limbs and the exclusion flag RIDE the hash sort
    # (three payload operands at one limb: 4.4 ms a 262 144-slot build
    # less than one packed row gather, docs/kernels.md)
    sorted_h, perm, *rl_s, excl_s = jax.lax.sort(
        (build_limb, jnp.arange(n, dtype=jnp.int32), *r_limbs,
         r_excl.astype(jnp.int8)), num_keys=1, is_stable=True)
    run_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_h[1:] != sorted_h[:-1]])

    # probe: the queries sorted INTO the build side's hash order, their
    # key limbs along; a query's rank there is its lower bound
    h_q = HL.hash_limbs(l_limbs, use_pallas=use_pallas) >> jnp.uint64(1)
    k, p, is_tab, before, kl = HL.merge_sorted(
        sorted_h, h_q, riders=list(zip(l_limbs, rl_s)))
    # its matches are the table's slots from it to the end of its hash
    # run (a query stands ahead of its equals): `before` never falls, so
    # a reversed running minimum over the run starts' reads the next one's
    run_k = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), k[1:] != k[:-1]])
    nxt = jnp.concatenate(
        [jax.lax.cummin(jnp.where(run_k, before, n), reverse=True)[1:],
         jnp.full((1,), n, jnp.int32)])
    cnt = nxt - before
    # exact verification: the run start's key limbs, the next table
    # slot's, must equal the probe's (a hash-only miss yields m = 0)
    hit = cnt > 0
    for at_start, own in zip(HL.fill_next(kl, is_tab), kl):
        hit = hit & (at_start == own)
    lo, m = HL.unmerge(p, [before, jnp.where(hit, cnt, 0)], q)

    # 64-bit collision between two distinct LIVE keys → exact fallback
    live = excl_s == 0
    key_neq = HL._adjacent_neq(rl_s)
    live_pair = jnp.concatenate(
        [jnp.zeros((1,), jnp.bool_), live[1:] & live[:-1]])
    ok = ~jnp.any((~run_start) & key_neq & live_pair)
    return m, lo, perm, ok
