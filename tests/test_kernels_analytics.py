"""Analytics kernels (radix histogram, hash aggregate, join probe) vs
oracles, including hypothesis property sweeps."""
import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.hash_aggregate import hash_aggregate, hash_aggregate_multi
from repro.kernels.hash_aggregate.ref import (hash_aggregate_multi_ref,
                                              hash_aggregate_ref)
from repro.kernels.join_probe import join_probe
from repro.kernels.join_probe.ref import join_probe_ref
from repro.kernels.radix_partition import (block_histograms,
                                           padded_bin_counts,
                                           radix_partition)
from repro.kernels.radix_partition.ref import block_histograms_ref


@pytest.mark.parametrize("n_bins,shift,block",
                         [(16, 0, 256), (64, 4, 512), (256, 8, 1024)])
def test_histograms_interpret(rng, n_bins, shift, block):
    keys = jnp.asarray(rng.randint(0, 1 << 24, block * 4), jnp.int32)
    ref = block_histograms_ref(keys, n_bins=n_bins, shift=shift, block=block)
    got = block_histograms(keys, n_bins=n_bins, shift=shift, block=block,
                           mode="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert int(np.asarray(got).sum()) == block * 4  # conservation


@pytest.mark.parametrize("shift", [0, 8, 16])
def test_histograms_negative_key_parity(rng, shift):
    """ref vs Pallas(interpret) on NEGATIVE keys — including the engine's
    -1 routed-padding sentinel. Digit extraction must be the LOGICAL
    shift in both implementations: an arithmetic shift smears the sign
    bit into every digit position above it, so -1 would land in a
    different bin per backend whenever shift > 0."""
    n_bins, block = 64, 256
    keys = rng.randint(-(1 << 24), 1 << 24, block * 4).astype(np.int32)
    keys[::7] = -1                    # the routing layer's padding key
    keys = jnp.asarray(keys)
    ref = block_histograms_ref(keys, n_bins=n_bins, shift=shift,
                               block=block)
    got = block_histograms(keys, n_bins=n_bins, shift=shift, block=block,
                           mode="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # oracle: logical shift == unsigned view of the same bit pattern
    digits = (np.asarray(keys).view(np.uint32) >> shift) & (n_bins - 1)
    np.testing.assert_array_equal(np.asarray(ref).sum(0),
                                  np.bincount(digits, minlength=n_bins))


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("n", [1, 255, 256, 257, 1000])
def test_padded_bin_counts_match_unpadded_oracle(rng, mode, n):
    """Block padding with the corrected sentinel bin is bit-exact against
    the unpadded bincount oracle at every misalignment (the engine's
    routed buffers are rarely block-aligned)."""
    for shift in (0, 8, 16):
        keys = rng.randint(-(1 << 24), 1 << 24, n).astype(np.int32)
        counts = padded_bin_counts(jnp.asarray(keys), n_bins=64,
                                   shift=shift, block=256, mode=mode)
        digits = (keys.view(np.uint32) >> shift) & 63
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.bincount(digits, minlength=64))


def test_padded_bin_counts_empty():
    counts = padded_bin_counts(jnp.zeros((0,), jnp.int32), n_bins=16,
                               block=256, mode="ref")
    np.testing.assert_array_equal(np.asarray(counts), np.zeros(16))


def test_radix_partition_unaligned_matches_oracle(rng):
    """N % block != 0 no longer drops to the bincount fallback: the
    padded kernel histogram must reproduce the oracle starts bit-exactly
    and keep the stable digit ordering."""
    keys_np = rng.randint(0, 1 << 16, 1000).astype(np.int32)
    keys = jnp.asarray(keys_np)
    ko, _vo, starts = radix_partition(keys, keys.astype(jnp.float32),
                                      n_bins=16, block=256, mode="ref")
    counts = np.bincount(keys_np & 15, minlength=16)
    np.testing.assert_array_equal(np.asarray(starts),
                                  np.cumsum(counts) - counts)
    digits = np.asarray(ko) & 15
    assert (np.diff(digits) >= 0).all()


def test_radix_partition_orders_digits(rng):
    keys = jnp.asarray(rng.randint(0, 1 << 16, 2048), jnp.int32)
    ko, vo, starts = radix_partition(keys, keys.astype(jnp.float32),
                                     n_bins=16, block=512, mode="ref")
    digits = np.asarray(ko) & 15
    assert (np.diff(digits) >= 0).all()
    # starts consistent with counts
    counts = np.bincount(np.asarray(keys) & 15, minlength=16)
    np.testing.assert_array_equal(np.asarray(starts),
                                  np.cumsum(counts) - counts)


@pytest.mark.parametrize("P,T,bins,block", [(2, 512, 128, 256),
                                            (4, 1024, 512, 512),
                                            (1, 256, 256, 128)])
def test_hash_aggregate_interpret(rng, P, T, bins, block):
    ids = jnp.asarray(rng.randint(0, bins, (P, T)), jnp.int32)
    vals = jnp.asarray(rng.rand(P, T), jnp.float32)
    ref = hash_aggregate_ref(ids, vals, n_bins=bins)
    got = hash_aggregate(ids, vals, n_bins=bins, block=block,
                         mode="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("P,T,bins,C,block", [(2, 512, 128, 3, 256),
                                              (4, 1024, 256, 7, 512),
                                              (1, 256, 128, 1, 128)])
def test_hash_aggregate_multi_interpret(rng, P, T, bins, C, block):
    """Fused multi-aggregate kernel vs oracle, incl. the C=1 edge."""
    ids = jnp.asarray(rng.randint(0, bins, (P, T)), jnp.int32)
    vals = jnp.asarray(rng.randn(P, C, T), jnp.float32)
    ref = hash_aggregate_multi_ref(ids, vals, n_bins=bins)
    got = hash_aggregate_multi(ids, vals, n_bins=bins, block=block,
                               mode="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)


def test_hash_aggregate_multi_matches_stacked_singles(rng):
    """The fused sweep equals C independent single-aggregate sweeps."""
    P, T, bins, C, block = 2, 768, 128, 4, 256
    ids = jnp.asarray(rng.randint(0, bins, (P, T)), jnp.int32)
    vals = jnp.asarray(rng.randn(P, C, T), jnp.float32)
    fused = hash_aggregate_multi(ids, vals, n_bins=bins, block=block,
                                 mode="interpret")
    for c in range(C):
        single = hash_aggregate(ids, vals[:, c], n_bins=bins, block=block,
                                mode="interpret")
        np.testing.assert_allclose(np.asarray(fused[:, c]),
                                   np.asarray(single), atol=1e-4)


def test_join_probe_interpret(rng):
    P, Bk, Pk = 3, 128, 512
    bk = jnp.asarray(np.stack([rng.permutation(4096)[:Bk]
                               for _ in range(P)]), jnp.int32)
    bv = jnp.asarray(rng.rand(P, Bk), jnp.float32)
    pk = jnp.asarray(rng.randint(0, 4096, (P, Pk)), jnp.int32)
    v_ref, f_ref = join_probe_ref(bk, bv, pk)
    v_got, f_got = join_probe(bk, bv, pk, block_p=128, mode="interpret")
    np.testing.assert_allclose(np.asarray(v_got), np.asarray(v_ref),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(f_got), np.asarray(f_ref))


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_histogram_conservation_property(data):
    """Property: histogram counts always sum to N and match bincount."""
    n_blocks = data.draw(st.integers(1, 4))
    block = data.draw(st.sampled_from([128, 256]))
    bits = data.draw(st.sampled_from([4, 6, 8]))
    seed = data.draw(st.integers(0, 2**31 - 1))
    r = np.random.RandomState(seed)
    keys = r.randint(0, 1 << 20, n_blocks * block).astype(np.int32)
    hist = np.asarray(block_histograms_ref(jnp.asarray(keys),
                                           n_bins=1 << bits, shift=0,
                                           block=block))
    assert hist.sum() == len(keys)
    np.testing.assert_array_equal(
        hist.sum(0), np.bincount(keys & ((1 << bits) - 1),
                                 minlength=1 << bits))
