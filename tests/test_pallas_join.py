"""Pallas hash-join probe kernel: correctness vs a numpy oracle in
interpret mode (runs on the CPU CI mesh; the real-TPU lowering is
compiled by tests/test_chip_compile.py)."""

import numpy as np
import pytest

import jax.numpy as jnp

from presto_tpu.ops import pallas_join as PJ


def oracle(build_keys, build_valid, probe_keys, probe_valid):
    lookup = {
        int(k): i
        for i, (k, v) in enumerate(zip(build_keys, build_valid)) if v
    }
    return np.array([
        lookup.get(int(k), -1) if v else -1
        for k, v in zip(probe_keys, probe_valid)
    ], dtype=np.int32)


@pytest.mark.parametrize("seed", [0, 1])
def test_probe_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    nb, np_ = 1000, 4096
    build = rng.choice(100000, size=nb, replace=False).astype(np.uint64)
    bvalid = rng.random(nb) < 0.9
    probe = rng.choice(100000, size=np_).astype(np.uint64)
    pvalid = rng.random(np_) < 0.95
    rid, overflow = PJ.join_unique(
        jnp.asarray(build), jnp.asarray(bvalid),
        jnp.asarray(probe), jnp.asarray(pvalid), interpret=True,
    )
    assert not bool(overflow)
    got = np.asarray(rid)
    want = oracle(build, bvalid, probe, pvalid)
    assert np.array_equal(got, want)


def test_probe_colliding_hashes():
    # keys crafted to collide in the table's low bits: chain probing must
    # still resolve every one of them
    build = np.arange(0, 64 * 1024, 1024, dtype=np.uint64)  # 64 keys
    bvalid = np.ones(64, bool)
    probe = np.concatenate([build, build + 1])  # half match, half miss
    pvalid = np.ones(128, bool)
    rid, overflow = PJ.join_unique(
        jnp.asarray(build), jnp.asarray(bvalid),
        jnp.asarray(probe), jnp.asarray(pvalid), interpret=True,
    )
    assert not bool(overflow)
    got = np.asarray(rid)
    assert np.array_equal(got[:64], np.arange(64, dtype=np.int32))
    assert np.all(got[64:] == -1)


def _ranges_oracle(bhash, bvalid, phash):
    """(start, count) per probe hash over the poison-sorted build order."""
    poisoned = np.where(bvalid, bhash, np.uint64(0xFFFFFFFFFFFFFFFF))
    order = np.argsort(poisoned, kind="stable")
    sh = poisoned[order]
    lo = np.searchsorted(sh, phash, side="left")
    hi = np.searchsorted(sh, phash, side="right")
    return lo.astype(np.int32), (hi - lo).astype(np.int32), order


@pytest.mark.parametrize(
    "layout,universe", [(("dim", 1), 48), (("dim", 16), 500),
                        (("dim", 32), 1000)]
)
def test_ranges_match_oracle(layout, universe):
    # duplicate keys: draws from a small universe so hash segments have
    # length > 1 (the table holds one entry a distinct hash, at most
    # half of tiles * 128); multi-tile layouts exercise the partitioned
    # tables
    rng = np.random.default_rng(3)
    nb, np_ = 1500, 4096
    bhash = rng.choice(universe, size=nb).astype(np.uint64) * np.uint64(
        0x9E3779B97F4A7C15
    )
    bvalid = rng.random(nb) < 0.9
    phash = np.concatenate([
        rng.choice(universe, size=np_ - 64).astype(np.uint64)
        * np.uint64(0x9E3779B97F4A7C15),
        rng.integers(1, 2**63, size=64, dtype=np.uint64),  # misses
    ])
    tabs, perm, overflow = PJ.build_index(
        jnp.asarray(bhash), jnp.asarray(bvalid), layout
    )
    assert not bool(overflow)
    start, cnt = PJ.probe_index(
        jnp.asarray(phash), tabs, layout, interpret=True
    )
    want_lo, want_cnt, want_order = _ranges_oracle(bhash, bvalid, phash)
    got_start, got_cnt = np.asarray(start), np.asarray(cnt)
    assert np.array_equal(got_cnt, want_cnt)
    hit = want_cnt > 0
    assert np.array_equal(got_start[hit], want_lo[hit])
    assert np.all(got_start[~hit] == -1)
    # the index's sorted order groups equal hashes contiguously
    sh = np.where(bvalid, bhash, np.uint64(0xFFFFFFFFFFFFFFFF))[
        np.asarray(perm)
    ]
    assert np.array_equal(sh, np.sort(sh))


def test_poison_hash_conflict_raises_overflow():
    # a VALID row whose hash equals the poison value (identity-encoded
    # BIGINT -1, or a 2^-64 real-hash collision) could interleave with
    # poisoned invalid rows and silently lose matches — build_index
    # must exclude it and raise the overflow escape so the query
    # retries on the exact sort join
    MAXH = np.uint64(0xFFFFFFFFFFFFFFFF)
    bhash = np.array([MAXH, 5, MAXH, 7], dtype=np.uint64)
    bvalid = np.array([True, False, True, True])
    layout = ("dim", 1)
    tabs, perm, overflow = PJ.build_index(
        jnp.asarray(bhash), jnp.asarray(bvalid), layout
    )
    assert bool(overflow)
    # the excluded rows are not in the table; ordinary segments intact
    start, cnt = PJ.probe_index(
        jnp.asarray(np.array([MAXH, 7, 6], dtype=np.uint64)),
        tabs, layout, interpret=True,
    )
    start, cnt = np.asarray(start), np.asarray(cnt)
    assert cnt[0] == 0  # MAX-hash rows excluded, not half-returned
    assert cnt[1] == 1 and cnt[2] == 0


def test_big_key_values():
    # full 64-bit keys (hash encodings) round-trip through the lo/hi split
    rng = np.random.default_rng(7)
    build = rng.integers(0, 2**63, size=256, dtype=np.uint64)
    build = np.unique(build)
    nb = len(build)
    probe = np.concatenate([build[: nb // 2],
                            rng.integers(0, 2**63, size=128,
                                         dtype=np.uint64)])
    pad = (-len(probe)) % 128
    probe = np.concatenate([probe, np.zeros(pad, np.uint64)])
    rid, overflow = PJ.join_unique(
        jnp.asarray(build), jnp.asarray(np.ones(nb, bool)),
        jnp.asarray(probe), jnp.asarray(np.ones(len(probe), bool)),
        interpret=True,
    )
    got = np.asarray(rid)
    assert np.array_equal(got[: nb // 2], np.arange(nb // 2))
