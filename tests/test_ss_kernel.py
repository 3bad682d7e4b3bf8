"""The chunk's Space-Saving kernel (kernels/ss_update.py) against the
per-event definition.

The reference is a `lax.scan` of `estimation.online_ss_update` over each
block, a `lax.cond` skipping pad lanes, in the order the chunked step and
`online_head_tables` use: snapshot, decay on a period boundary, offer.
Every case routes two chunks, the second from the first's final state and
block counter, so a summary and a `b0 > 0` carried in from an earlier chunk
are covered everywhere.  Kernels run in interpret mode on the CPU.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core.estimation import (
    online_ss_decay,
    online_ss_init,
    online_ss_update,
)
from repro.core.streams import zipf_stream
from repro.kernels.ss_update import ss_update_chunk

NBLK, BLOCK = 4, 32  # 128 events per chunk
DECAY = 200  # not a multiple of BLOCK: periods open mid-way between blocks


@functools.partial(jax.jit, static_argnames=("decay_period",))
def _reference(state, keys, valid, b0, decay_period):
    block = keys.shape[1]

    def blk(s, inp):
        kb, vb, b = inp
        snap = (s.keys, s.counts, s.total)
        if decay_period > 0:
            do = (b * block) % decay_period < block
            s = lax.cond((b > 0) & do, online_ss_decay, lambda s: s, s)

        def upd(s, kv):
            k, v = kv
            return lax.cond(
                v > 0, lambda s: online_ss_update(s, k), lambda s: s, s
            ), None

        return lax.scan(upd, s, (kb, vb))[0], snap

    b = b0 + jnp.arange(keys.shape[0], dtype=jnp.int32)
    final, (sk, sc, st) = lax.scan(blk, state, (keys, valid, b))
    return sk, sc, st, final


def _stream(kind, capacity, n, seed):
    if kind == "zipf":  # a few hot keys: hits dominate
        return zipf_stream(n, 400, 1.6, seed=seed).astype(np.int32)
    # capacity + 1 keys in turn: every offer misses, counts tie throughout,
    # and the lowest-slot rule alone picks each victim
    return (np.arange(n) % (capacity + 1) + 10).astype(np.int32)


CASES = [
    pytest.param(8, 0, "zipf", False, 0, id="cap8-zipf"),
    pytest.param(8, DECAY, "ties", True, 0, id="cap8-ties-decay-padded"),
    pytest.param(64, 0, "ties", True, 0, id="cap64-ties-padded"),
    pytest.param(64, DECAY, "zipf", False, 7, id="cap64-zipf-decay-b0"),
    pytest.param(256, DECAY, "zipf", True, 0, id="cap256-zipf-decay-padded"),
    pytest.param(300, 0, "zipf", True, 3, id="cap300-zipf-padded-b0"),
]


@pytest.mark.parametrize("capacity,decay_period,kind,padded,b0", CASES)
def test_kernel_eq_per_event_scan(capacity, decay_period, kind, padded, b0):
    n = 2 * NBLK * BLOCK
    keys = _stream(kind, capacity, n, seed=capacity).reshape(2, NBLK, BLOCK)
    valid = np.ones_like(keys)
    if padded:  # the stream ends 9 lanes into the second chunk's last block
        valid[1, -1, 9:] = 0
    got_state = want_state = online_ss_init(capacity)
    for c in range(2):
        b = jnp.int32(b0 + c * NBLK)
        k, v = jnp.asarray(keys[c]), jnp.asarray(valid[c])
        *got, got_state = ss_update_chunk(
            got_state, k, v, b, decay_period=decay_period
        )
        *want, want_state = _reference(want_state, k, v, b, decay_period)
        for g, w in zip(got + list(got_state), want + list(want_state)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert int(got_state.total) > 0
