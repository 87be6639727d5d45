"""The paged KV block-pool helpers (`models/layers.py`) against plain
NumPy loops over the block table, bit for bit: the view gathers each
lane's blocks, the decode write puts one token per lane, the prefill
scatter puts whole row blocks; ids outside the pool read and write the
trash block and nothing else."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import (TRASH_BLOCK, paged_pool_view,
                                 paged_pool_write, paged_scatter_rows)

NB, H, D = 24, 2, 3


def _pool(rng, bs, dtype=np.float32):
    return rng.standard_normal((NB, bs, H, D)).astype(dtype)


def _owned_table(rng, B, MBL, live):
    """(B, MBL) table: each lane owns its first `live[b]` slots with
    distinct live ids (never the trash block), the rest point at trash."""
    ids = iter(rng.permutation(np.arange(1, NB)))
    table = np.full((B, MBL), TRASH_BLOCK, np.int32)
    for b, n in enumerate(live):
        for j in range(n):
            table[b, j] = next(ids)
    return table


def _fix(bid):
    return bid if 0 <= bid < NB else TRASH_BLOCK


def _view_ref(pool, table):
    B, MBL = table.shape
    bs = pool.shape[1]
    out = np.empty((B, MBL * bs, H, D), pool.dtype)
    for b in range(B):
        for j in range(MBL):
            out[b, j * bs:(j + 1) * bs] = pool[_fix(table[b, j])]
    return out


def _write_ref(pool, table, lane_pos, vals):
    """The write as a loop, and the {(block, offset): lanes} it made."""
    pool = pool.copy()
    MBL, bs = table.shape[1], pool.shape[1]
    hits = {}
    for b, pos in enumerate(lane_pos):
        j = min(max(pos // bs, 0), MBL - 1)
        bid, off = _fix(table[b, j]), pos % bs
        pool[bid, off] = vals[b, 0]
        hits.setdefault((bid, off), []).append(b)
    return pool, hits


def _scatter_ref(pool, rows, scatter_table):
    pool = pool.copy()
    bs = pool.shape[1]
    for r in range(rows.shape[0]):
        for j, bid in enumerate(scatter_table[r]):
            blk = rows[r, j * bs:(j + 1) * bs]
            pool[_fix(bid), :len(blk)] = blk
            pool[_fix(bid), len(blk):] = 0
    return pool


@pytest.mark.parametrize("bs", [1, 4, 16])
@pytest.mark.parametrize("case", ["owned", "corrupt", "idle"])
def test_view_matches_loop(bs, case):
    rng = np.random.default_rng(bs)
    B, MBL = 4, 5
    pool = _pool(rng, bs)
    table = _owned_table(rng, B, MBL, [5, 3, 1, 0] if case != "idle"
                         else [0] * B)
    if case == "corrupt":
        table[0, 1], table[1, 0], table[2, 4] = -1, NB, NB + 100
        table[3, 2] = np.iinfo(np.int32).min
    got = np.asarray(jax.jit(paged_pool_view)(jnp.asarray(pool),
                                              jnp.asarray(table)))
    want = _view_ref(pool, table)
    assert got.shape == (B, MBL * bs, H, D)
    np.testing.assert_array_equal(got, want)
    # slot t of a lane holds absolute position t of its owned blocks
    if case == "owned":
        np.testing.assert_array_equal(got[0, bs:2 * bs], pool[table[0, 1]])


@pytest.mark.parametrize("bs", [1, 4, 16])
@pytest.mark.parametrize("where", ["first", "last", "across", "past_table"])
@pytest.mark.parametrize("case", ["owned", "corrupt", "idle"])
def test_write_matches_loop(bs, where, case):
    rng = np.random.default_rng(100 * bs + len(where))
    B, MBL = 5, 4
    pool = _pool(rng, bs, np.float32)
    table = _owned_table(rng, B, MBL, [4, 4, 2, 1, 3] if case != "idle"
                         else [0] * B)
    if case == "corrupt":
        table[:, 0] = [-7, NB, NB + 3, np.iinfo(np.int32).max, -1]
    # positions on a block's first or last offset, straddling an edge
    # (lanes alternate between the two sides of it), or past the table
    edge = {"first": [0, bs, 2 * bs, 0, bs],
            "last": [bs - 1, 2 * bs - 1, bs - 1, 3 * bs - 1, 4 * bs - 1],
            "across": [bs - 1, bs, 2 * bs - 1, 2 * bs, 3 * bs],
            "past_table": [MBL * bs, MBL * bs + bs - 1, 0, 5 * MBL * bs,
                           MBL * bs - 1]}[where]
    lane_pos = np.asarray(edge, np.int32)
    vals = rng.standard_normal((B, 1, H, D)).astype(np.float32)
    got = np.array(jax.jit(paged_pool_write)(
        jnp.asarray(pool), jnp.asarray(table), jnp.asarray(lane_pos),
        jnp.asarray(vals)))
    want, hits = _write_ref(pool, table, lane_pos, vals)
    # every live block, written or not, is exactly the loop's
    np.testing.assert_array_equal(got[1:], want[1:])
    # a trash slot several lanes wrote holds one of their values; the
    # rest of the trash block is the loop's
    shared = {k: v for k, v in hits.items()
              if k[0] == TRASH_BLOCK and len(v) > 1}
    for (bid, off), lanes in shared.items():
        assert any((got[bid, off] == vals[b, 0]).all() for b in lanes)
        got[bid, off] = want[bid, off]
    np.testing.assert_array_equal(got[TRASH_BLOCK], want[TRASH_BLOCK])
    if case == "idle":
        np.testing.assert_array_equal(got[1:], pool[1:])


@pytest.mark.parametrize("bs", [1, 4, 16])
@pytest.mark.parametrize("S", [1, 16, 21])
def test_write_leaves_other_blocks(bs, S):
    """One lane writes at position S - 1: only its (block, offset) slot
    changes, every other slot of every live block keeps its bits."""
    rng = np.random.default_rng(S)
    MBL = -(-32 // bs)
    table = _owned_table(rng, 1, MBL, [min(MBL, NB - 1)])
    pool = _pool(rng, bs)
    vals = rng.standard_normal((1, 1, H, D)).astype(np.float32)
    got = np.asarray(paged_pool_write(jnp.asarray(pool), jnp.asarray(table),
                                      jnp.asarray([S - 1], jnp.int32),
                                      jnp.asarray(vals)))
    changed = np.argwhere((got != pool).any(axis=(2, 3)))
    blk, off = divmod(S - 1, bs)
    bid = table[0, blk] if blk < MBL else TRASH_BLOCK
    assert changed.tolist() == [[bid, off]]
    np.testing.assert_array_equal(got[bid, off], vals[0, 0])


@pytest.mark.parametrize("bs", [1, 4, 16])
@pytest.mark.parametrize("S", [1, 5, 16, 19])
@pytest.mark.parametrize("case", ["owned", "corrupt", "padding_row"])
def test_scatter_rows_matches_loop(bs, S, case):
    rng = np.random.default_rng(bs * 31 + S)
    Bp = 3
    nb = -(-S // bs)
    pool = _pool(rng, bs)
    live = [min(nb, (NB - 1) // Bp)] * Bp
    live[1] = max(live[1] - 1, 0)      # row 1 owns one block fewer
    if case == "padding_row":
        live[2] = 0
    table = _owned_table(rng, Bp, nb, live)
    if case == "corrupt":
        table[0, 0], table[1, -1] = -3, NB + 1
    rows = rng.standard_normal((Bp, S, H, D)).astype(np.float32)
    got = np.asarray(jax.jit(paged_scatter_rows)(
        jnp.asarray(pool), jnp.asarray(rows), jnp.asarray(table)))
    want = _scatter_ref(pool, rows, table)
    np.testing.assert_array_equal(got[1:], want[1:])
    untouched = np.setdiff1d(np.arange(1, NB), table)
    np.testing.assert_array_equal(got[untouched], pool[untouched])
    # the trash block takes only what was sent to it
    if not (table == TRASH_BLOCK).any() and case == "owned":
        np.testing.assert_array_equal(got[TRASH_BLOCK], pool[TRASH_BLOCK])


def test_write_then_view_reads_the_token_back():
    """A decode step's write, then the view: each owned lane sees its
    new token at its own position, in the pool's dtype."""
    rng = np.random.default_rng(7)
    bs, B, MBL = 4, 3, 3
    pool = _pool(rng, bs, np.float32).astype(jnp.bfloat16)
    table = _owned_table(rng, B, MBL, [3, 2, 0])
    lane_pos = np.asarray([9, 4, 2], np.int32)
    vals = rng.standard_normal((B, 1, H, D)).astype(np.float32)
    pl = paged_pool_write(jnp.asarray(pool), jnp.asarray(table),
                          jnp.asarray(lane_pos), jnp.asarray(vals))
    assert pl.dtype == jnp.bfloat16
    view = np.asarray(paged_pool_view(pl, jnp.asarray(table)))
    for b in (0, 1):
        np.testing.assert_array_equal(
            view[b, lane_pos[b]],
            np.asarray(jnp.asarray(vals[b, 0]).astype(jnp.bfloat16)))
