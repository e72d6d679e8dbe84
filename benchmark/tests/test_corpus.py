"""The corpus, drawn block by block on one device or row-sharded over
four, is the one-program draw of ``jax.random.normal``, bit for bit."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.corpus import blobs

CORPUS = {"generator": "blobs", "rows": 20_000, "queries": 2_000, "dim": 96,
          "clusters": 50, "std": 5.0, "center_box": [-10.0, 10.0],
          "seed": 0}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def one_program(key, n, dim, n_clusters, std, lo, hi):
    """``rows + queries`` rows in one program: how the corpus was drawn
    before it could be sharded, and what fixes its values."""
    centers_key, kl, kn = jax.random.split(key, 3)
    centers = jax.random.uniform(centers_key, (n_clusters, dim), jnp.float32,
                                 minval=lo, maxval=hi)
    labels = jax.random.permutation(
        kl, jnp.arange(n, dtype=jnp.int32) % n_clusters)
    noise = std * jax.random.normal(kn, (n, dim), jnp.float32)
    return jnp.take(centers, labels, axis=0) + noise


@pytest.mark.parametrize("rows,block", [(20_000, blobs.BLOCK_ROWS),
                                        (20_004, 1000)])
@pytest.mark.parametrize("shards", [1, 4])
def test_four_shards_draw_the_one_device_rows(monkeypatch, rows, block,
                                              shards):
    """The second case draws each shard in blocks, the last overlapping
    the one before it."""
    monkeypatch.setattr(blobs, "BLOCK_ROWS", block)
    c = dict(CORPUS, rows=rows, shards=shards)
    want = np.asarray(one_program(
        jax.random.PRNGKey(c["seed"]), rows + c["queries"], c["dim"],
        c["clusters"], c["std"], *c["center_box"]))
    got, pool = blobs.make(c)
    assert len({s.device for s in got.addressable_shards}) == shards
    assert got.shape == (rows, 96)
    np.testing.assert_array_equal(np.asarray(got), want[:rows])
    np.testing.assert_array_equal(pool, want[rows:])


def test_counters_carry_past_two_to_the_32():
    first, count, dim = 50_000_000 - 7, 5, 96
    hi, lo = blobs._counters(jnp.int32(first), count, dim)
    want = (np.arange(first, first + count, dtype=np.uint64)[:, None]
            * np.uint64(dim) + np.arange(dim, dtype=np.uint64)[None, :])
    got = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) \
        | np.asarray(lo).astype(np.uint64)
    np.testing.assert_array_equal(got, want)
    assert (want >> np.uint64(32)).max() > 0


def test_normal_rows_are_those_of_jax_random_normal():
    key = jax.random.PRNGKey(11)
    full = jax.random.normal(key, (300, 96), jnp.float32)
    part = blobs._normal_rows(key, jnp.int32(123), 45, 96)
    np.testing.assert_array_equal(np.asarray(part), np.asarray(full[123:168]))


def test_shards_need_rows_they_divide():
    with pytest.raises(ValueError):
        blobs.make(dict(CORPUS, rows=20_001, shards=4))
