"""The packed tick and the packed prefill layout are ONE definition
(`serving/decoder.py`) that both decoders run: here they are held to
hand-worked values over a toy forward, with no model."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.serving import decoder as D
from paddle_tpu.serving import mla_decoder as M


def _tick(counters):
    """Rows: 0 decodes, 1 holds 5 prompt tokens (chunk cap 3), 2 is frozen,
    3 holds 2 prompt tokens (its last chunk: it emits)."""
    tokens = jnp.asarray([7, 0, 9, 0], jnp.int32)
    lens = jnp.asarray([10, 4, 6, 0], jnp.int32)
    done = jnp.asarray([False, False, True, False])
    remaining = jnp.asarray([1, 5, 5, 5], jnp.int32)
    pend = jnp.asarray([[0] * 6, [11, 12, 13, 14, 15, 0], [0] * 6,
                        [21, 22, 0, 0, 0, 0]], jnp.int32)
    pend_n = jnp.asarray([0, 5, 0, 2], jnp.int32)
    seen = {}

    def forward(lay, pools):
        seen["lay"] = lay
        # the "model": a row's next token is 100 + its last stream token
        nxt = 100 + lay.ptok[lay.last_idx]
        return nxt, (pools[0] + 1,), counters

    carry = (tokens, lens, done, remaining, pend, pend_n, jnp.int32(0))
    out, (nxt, emit, real) = D.packed_tick(
        carry, jnp.int32(3), jnp.int32(-1), t=8, capacity=12,
        forward=forward)
    return seen["lay"], out, nxt, emit, real


def test_the_tick_lays_the_stream_out_and_keeps_the_rules():
    lay, out, nxt, emit, real = _tick(())
    # stream: row 0 one token, row 1 three, row 2 none, row 3 two; 2 padding
    assert lay.nl.tolist() == [1, 3, 0, 2]
    assert lay.rows.tolist()[:6] == [0, 1, 1, 1, 3, 3]
    assert lay.ptok.tolist() == [7, 11, 12, 13, 21, 22, 0, 0]
    assert lay.pos.tolist()[:6] == [10, 4, 5, 6, 0, 1]
    # position 10 < capacity 12 is written, padding is not
    assert lay.write_ok.tolist() == [True] * 6 + [False] * 2
    assert lay.last_idx.tolist()[:2] == [0, 3] and lay.last_idx[3] == 5
    assert lay.true.tolist() == [11, 7, 6, 2]
    assert lay.live.tolist() == [True, True, False, True]
    assert lay.is_pf.tolist() == [False, True, False, True]
    tokens, lens, done, remaining, pend, pend_n, pool = out
    # rows 0 and 3 emit (3's prompt ends in this chunk); row 1 does not
    assert emit.tolist() == [True, False, False, True]
    assert tokens.tolist() == [107, 0, 9, 122]
    assert lens.tolist() == [11, 7, 6, 2]
    assert remaining.tolist() == [0, 5, 5, 4]
    assert done.tolist() == [True, False, True, False]   # row 0's budget
    assert pend[1].tolist() == [14, 15, 0, 0, 0, 0]
    assert pend_n.tolist() == [0, 2, 0, 0]
    assert int(pool) == 1 and int(real) == 6 and real.shape == ()


def test_a_decoders_counters_ride_the_real_block():
    _, _, _, _, real = _tick((jnp.int32(40), jnp.int32(2)))
    assert real.tolist() == [6, 40, 2]


def test_the_prefill_layout_by_hand():
    lay = D.packed_prefill_layout(
        [([5, 6, 7], 8, [3, 4]), ([9], 0, [1])], slots=4, max_pages=3,
        page_size=4, scratch=33)
    assert (lay.t, lay.window) == (4, 4)
    assert lay.ptok.tolist() == [5, 6, 7, 9]
    assert lay.pos.tolist() == [8, 9, 10, 0]
    assert lay.rows.tolist() == [0, 0, 0, 1]
    assert lay.ok.tolist() == [True] * 4
    assert lay.table.tolist() == [[3, 4, 33], [1, 33, 33], [33] * 3,
                                  [33] * 3]
    assert lay.last_idx.tolist() == [2, 3, 0, 0]
    assert lay.sample_pos.tolist()[:2] == [10, 0]
    assert lay.live.tolist() == [True, True, False, False]
    assert lay.new.tolist() == [3, 1, 0, 0]
    # a position past the table's capacity (3 pages x 4) goes to scratch
    far = D.packed_prefill_layout([([1, 2], 11, [0, 1, 2])], 2, 3, 4, 9)
    assert far.ok.tolist() == [True, False]


@pytest.mark.parametrize("name", ["packed_tick", "packed_prefill_layout"])
def test_both_decoders_run_the_one_definition(name):
    assert getattr(M, name) is getattr(D, name)
