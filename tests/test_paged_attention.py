"""The paged-attention kernel (``ops/pallas/paged_attention.py``) against
the plain form it stands in for on the chip,
``_attend_rows(q, gather_pages(k), gather_pages(v), valid)`` under
``GptBlock.decode_step_paged``'s mask, in float32 to 1e-5.

On the CPU the kernel runs under the TPU interpreter, which executes the
page copies, their semaphores and the DYNAMIC trip counts as written (this
JAX's interpreter lowers them: no all-pages form was needed), at toy sizes:
heads of 128 (the kernel's lanes) and heads of 64 and 32 (two and four kv
heads a lane tile, the query widened to the tile: the scores are ``q . k``
exactly), pages of 16 rows, chunks of 8 pages so
that a long lane walks several chunks and ends inside one.  Every page no
lane of a case holds is NaN: the kernel copies no such page, and the plain
form, which reads only held pages and the sentinel's zeros, is computed on
the same pools.

The LATENT kernel of the same file (PR 47) likewise against
``GptBlock.latent_decode_step_paged``'s plain form from the mask on: one
pool of latents that are keys and values both, one of rotated keys held
two tokens a row of 128 lanes, 20 heads over the one shared row.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.ops.pallas import paged_attention as paged_ops

PAGE, POOL = 16, 66
S = POOL                                        # the sentinel


def plain(q, k_pool, v_pool, table, positions, kv_heads, window):
    """``decode_step_paged``'s CPU path from the mask on."""
    B, H, D = q.shape
    cfg = gpt_lib.GptConfig(
        vocab_size=8, hidden_size=H * D, num_layers=1, num_heads=H,
        kv_heads=kv_heads, intermediate_size=8, max_position=8,
        dtype="float32")
    block = gpt_lib.GptBlock(cfg, gpt_lib.FULL_ATTENTION, False)
    MP = table.shape[1]
    s = jnp.arange(MP * PAGE)
    allocated = jnp.take_along_axis(table, s[None, :] // PAGE,
                                    axis=1) < k_pool.shape[0] - 1
    if window:
        behind = (positions[:, None] - s[None, :]) % (MP * PAGE)
        valid = ((behind < window) & (behind <= positions[:, None])
                 & allocated)
    else:
        valid = (s[None, :] <= positions[:, None]) & allocated
    return block._attend_rows(q[:, None], gpt_lib.gather_pages(k_pool, table),
                              gpt_lib.gather_pages(v_pool, table),
                              valid)[:, 0]


def table_of(lanes, MP, pool=POOL):
    """A table whose lane ``b`` holds ``lanes[b]`` pages, drawn without
    repeats from a shuffled pool of ``pool`` pages (a lane's pages are not
    neighbours); ``pool`` itself is the sentinel."""
    pages = iter(np.random.default_rng(45).permutation(pool).tolist())
    table = np.full((len(lanes), MP), pool, np.int32)
    for b, n in enumerate(lanes):
        table[b, :n] = [next(pages) for _ in range(n)]
    return table


#: name: heads, kv heads, window, table width, pages held a lane, positions,
#: loop step (of 3, or None), and the head's size where it is not 128.
CASES = {
    # A lane of three chunks that ends inside one; an idle lane; a lane of
    # ONE token; a length that ends exactly on a page's last row (and the
    # chunk's); a lane that ends on a chunk's first row.
    "full-grouped": (4, 2, 0, 24, [20, 0, 1, 8, 9],
                     [20 * PAGE - 3, 0, 0, 8 * PAGE - 1, 8 * PAGE], None),
    "full-ungrouped": (3, 3, 0, 24, [20, 0, 1, 8, 9],
                       [20 * PAGE - 3, 0, 0, 8 * PAGE - 1, 8 * PAGE], None),
    # A ring of 9 pages (144 rows) under a window of 128: a lane inside
    # the window (2 pages held), one just past it, one that has gone round
    # twice, an idle one, and one on the ring's last row.
    "ring-grouped": (4, 1, 128, 9, [2, 9, 9, 0, 9],
                     [20, 131, 2 * 144 + 77, 0, 143], None),
    "ring-ungrouped": (2, 2, 128, 9, [2, 9, 9, 0, 9],
                       [20, 131, 2 * 144 + 77, 0, 143], None),
    # The looped form: the pool holds three runs of POOL // 3 pages, the
    # step reads run 2 through ``loop_step_pages``'s offset table, whose
    # sentinel is the pool's last page.
    "looped": (2, 2, 0, 12, [10, 0, 3], [10 * PAGE - 1, 0, 33], 2),
    # A HOLE in a lane's walk (no engine makes one): the entry reads the
    # sentinel's zeros and its rows do not count, as in the plain form.
    "hole": (4, 2, 0, 24, [12, 2], [12 * PAGE - 2, 17], None),
    # Heads of 64: 8 query heads in groups of 4 over 2 kv heads, ONE lane
    # tile a row (the tile's two halves are two kv heads), and 4 kv heads
    # in two tiles; a ring at that head; and heads of 32, four a tile.
    "full-head64-one-tile": (8, 2, 0, 24, [20, 0, 1, 8, 9],
                             [20 * PAGE - 3, 0, 0, 8 * PAGE - 1, 8 * PAGE],
                             None, 64),
    "full-head64-two-tiles": (8, 4, 0, 24, [20, 0, 1, 8, 9],
                              [20 * PAGE - 3, 0, 0, 8 * PAGE - 1, 8 * PAGE],
                              None, 64),
    "ring-head64": (4, 2, 128, 9, [2, 9, 9, 0, 9],
                    [20, 131, 2 * 144 + 77, 0, 143], None, 64),
    "full-head32": (8, 4, 0, 24, [20, 0, 3], [20 * PAGE - 3, 0, 33],
                    None, 32),
    # 28 query heads in groups of SEVEN over 4 kv heads of 128 (a group
    # that divides no power of two; 28 rows are no whole sublane tiles):
    # the full table, and a ring with a lane inside the window beside
    # lanes gone round.
    "full-groups-of-7": (28, 4, 0, 24, [20, 0, 1, 8, 9],
                         [20 * PAGE - 3, 0, 0, 8 * PAGE - 1, 8 * PAGE],
                         None),
    "ring-groups-of-7": (28, 4, 128, 9, [2, 9, 9, 0, 9],
                         [20, 131, 2 * 144 + 77, 0, 143], None),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_gives_what_the_plain_form_gives(name, monkeypatch):
    H, G, window, MP, lanes, positions, loop_step, *head = CASES[name]
    D = head[0] if head else 128
    monkeypatch.setattr(paged_ops, "_CHUNK_MAX", 128)    # 8 pages a chunk
    keys = jax.random.split(jax.random.key(45), 3)
    q = jax.random.normal(keys[0], (len(lanes), H, D), jnp.float32)
    k_pool, v_pool = (
        jax.random.normal(key, (POOL + 1, PAGE, G * D), jnp.float32)
        .at[-1].set(0) for key in keys[1:])
    if loop_step is None:
        table = table_of(lanes, MP)
    else:
        table = np.array(gpt_lib.loop_step_pages(
            jnp.asarray(table_of(lanes, MP, POOL // 3)), loop_step,
            POOL + 1, 3))
        assert table.max() == S and table.min() >= loop_step * (POOL // 3)
    if name == "hole":
        table[0, 3] = table[0, 9] = S
    table = table.astype(np.int32)
    held = np.zeros(POOL + 1, bool)
    held[table[table < S]] = True
    held[S] = True                          # nobody's, and zeros
    poison = jnp.asarray(~held)[:, None, None]
    k_pool, v_pool = (jnp.where(poison, jnp.nan, x) for x in (k_pool, v_pool))
    positions = np.asarray(positions, np.int32)
    want = np.asarray(plain(q, k_pool, v_pool, jnp.asarray(table),
                            jnp.asarray(positions), G, window))
    got = np.asarray(jax.jit(
        lambda *a: paged_ops.paged_attention(*a, window=window))(
            q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(positions)))
    assert got.shape == want.shape == (len(lanes), H, D)
    assert np.isfinite(want).all() and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    for b, n in enumerate(lanes):
        # an idle lane gives zeros; a seated one something
        assert (np.abs(got[b]).max() > 1e-3) == (n > 0)
    # the walk the host counts is the walk the kernel makes
    walked = paged_ops.pages_walked(table, positions, S, PAGE)
    assert walked.tolist() == [
        0 if n == 0 else min(n, p // PAGE + 1)
        for n, p in zip(lanes, positions)]


def test_pools_the_kernel_cannot_walk_are_refused():
    """A float8 page of 16 rows is half a tile; a head that neither fills
    lane tiles of 128 nor divides one, or whose flat row is not whole
    tiles (three kv heads of 64), cannot be presented as heads of 128:
    ``supports`` says so and the program keeps the plain form there."""
    ok = jax.ShapeDtypeStruct((9, 16, 256), jnp.bfloat16)
    assert paged_ops.supports(ok, 128)
    assert paged_ops.supports(ok, 64) and paged_ops.supports(ok, 32)
    assert not paged_ops.supports(
        jax.ShapeDtypeStruct((9, 16, 192), jnp.bfloat16), 64)
    assert not paged_ops.supports(
        jax.ShapeDtypeStruct((9, 16, 384), jnp.bfloat16), 96)
    assert not paged_ops.supports(
        jax.ShapeDtypeStruct((9, 16, 256), jnp.float8_e4m3fn), 128)
    assert not paged_ops.supports(
        jax.ShapeDtypeStruct((9, 12, 256), jnp.bfloat16), 128)
    with pytest.raises(ValueError, match="cannot walk"):
        paged_ops.paged_attention(
            jnp.zeros((1, 3, 64)), jnp.zeros((9, 16, 192)),
            jnp.zeros((9, 16, 192)), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32))


# ------------------------------------------------- one latent row a token


def plain_latent(q_lat, q_rot, latent_pool, key_pool, table, positions,
                 scale):
    """``latent_decode_step_paged``'s CPU path from the mask on."""
    B, MP = table.shape
    rope = q_rot.shape[-1]
    s = jnp.arange(MP * PAGE)
    allocated = jnp.take_along_axis(table, s[None, :] // PAGE,
                                    axis=1) < latent_pool.shape[0] - 1
    valid = (s[None, :] <= positions[:, None]) & allocated
    latents = gpt_lib.gather_pages(latent_pool, table)
    keys = paged_ops.unpack_keys(
        gpt_lib.gather_pages(key_pool, table).reshape(
            B, MP, *key_pool.shape[1:]), rope).reshape(B, MP * PAGE, rope)
    logits = (jnp.einsum("bhc,bsc->bhs", q_lat, latents)
              + jnp.einsum("bhr,bsr->bhs", q_rot, keys)) * scale
    logits = jnp.where(valid[:, None, :], logits, jnp.finfo(jnp.float32).min)
    return jnp.einsum("bhs,bsc->bhc", jax.nn.softmax(logits, axis=-1),
                      latents)


#: name: heads, the latents' width, table width, pages held a lane,
#: positions, the table's entries made holes.
LATENT_CASES = {
    # 20 heads (padded to 24 in the wrapper, the padding dropped); a lane
    # of three chunks that ends inside one; an idle lane; a lane of ONE
    # token; a length that ends exactly on a page's last row (and the
    # chunk's); a lane that ends on a chunk's first row.
    "heads20": (20, 256, 24, [20, 0, 1, 8, 9],
                [20 * PAGE - 3, 0, 0, 8 * PAGE - 1, 8 * PAGE], ()),
    # Whole tiles of heads at the published width of 512.
    "heads8-wide": (8, 512, 24, [17, 3], [17 * PAGE - 9, 40], ()),
    # A HOLE in a lane's walk (no engine makes one).
    "hole": (20, 128, 24, [12, 2], [12 * PAGE - 2, 17], ((0, 3), (0, 9))),
}


@pytest.mark.parametrize("name", list(LATENT_CASES))
def test_the_latent_kernel_gives_what_the_plain_form_gives(name, monkeypatch):
    H, C, MP, lanes, positions, holes = LATENT_CASES[name]
    rope, scale = 64, 1.0 / (192 + 64) ** 0.5
    monkeypatch.setattr(paged_ops, "_CHUNK_MAX", 128)    # 8 pages a chunk
    keys = jax.random.split(jax.random.key(47), 4)
    q_lat = jax.random.normal(keys[0], (len(lanes), H, C), jnp.float32)
    q_rot = jax.random.normal(keys[1], (len(lanes), H, rope), jnp.float32)
    latent_pool = jax.random.normal(
        keys[2], (POOL + 1, PAGE, C), jnp.float32).at[-1].set(0)
    rows = paged_ops.key_rows(PAGE, rope)
    key_pool = paged_ops.pack_keys(jax.random.normal(
        keys[3], (POOL + 1, PAGE, rope), jnp.float32).at[-1].set(0), rows)
    assert key_pool.shape == (POOL + 1, PAGE // 2, 128)
    assert paged_ops.supports_latent(latent_pool, key_pool)
    table = table_of(lanes, MP)
    for hole in holes:
        table[hole] = S
    held = np.zeros(POOL + 1, bool)
    held[table[table < S]] = True
    held[S] = True                          # nobody's, and zeros
    poison = jnp.asarray(~held)[:, None, None]
    latent_pool, key_pool = (jnp.where(poison, jnp.nan, x)
                             for x in (latent_pool, key_pool))
    positions = np.asarray(positions, np.int32)
    args = (q_lat, q_rot, latent_pool, key_pool, jnp.asarray(table),
            jnp.asarray(positions))
    want = np.asarray(plain_latent(*args, scale))
    got = np.asarray(jax.jit(lambda *a: paged_ops.latent_paged_attention(
        *a, scale=scale))(*args))
    assert got.shape == want.shape == (len(lanes), H, C)
    assert np.isfinite(want).all() and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    for b, n in enumerate(lanes):
        assert (np.abs(got[b]).max() > 1e-3) == (n > 0)


def test_rotated_keys_lie_two_tokens_a_row_of_whole_lanes():
    """Token ``o`` of a page of 16 in row ``o % 8`` at lanes ``o // 8 *
    64``; a shape that does not pack (a rope of 8 in a page of 8: the
    rehearsal's) stays a row a token; packing is undone exactly."""
    assert paged_ops.key_rows(16, 64) == 8 and paged_ops.key_rows(32, 64) == 16
    assert paged_ops.key_rows(8, 8) == 8 and paged_ops.key_rows(4, 8) == 4
    keys = jnp.arange(3 * 16 * 64, dtype=jnp.float32).reshape(3, 16, 64)
    packed = paged_ops.pack_keys(keys, 8)
    assert packed.shape == (3, 8, 128)
    for o in (0, 5, 8, 15):
        np.testing.assert_array_equal(
            packed[1, o % 8, o // 8 * 64:o // 8 * 64 + 64], keys[1, o])
    np.testing.assert_array_equal(paged_ops.unpack_keys(packed, 64), keys)
    assert paged_ops.pack_keys(keys, 16) is keys
    assert paged_ops.unpack_keys(keys, 64) is keys


def test_latent_pools_the_kernel_cannot_walk_are_refused():
    """A float8 page of 16 rows is half a tile, latents that do not fill
    lane tiles or rotated keys a row a token cannot be walked:
    ``supports_latent`` says so and the program keeps the plain form."""
    pools = lambda dtype, c=512, keys=(8, 128): (  # noqa: E731
        jax.ShapeDtypeStruct((9, 16, c), dtype),
        jax.ShapeDtypeStruct((9, *keys), dtype))
    assert paged_ops.supports_latent(*pools(jnp.bfloat16))
    assert paged_ops.supports_latent(*pools(jnp.float32))
    assert not paged_ops.supports_latent(*pools(jnp.float8_e4m3fn))
    assert not paged_ops.supports_latent(*pools(jnp.bfloat16, c=576))
    assert not paged_ops.supports_latent(*pools(jnp.bfloat16, keys=(16, 64)))
    assert not paged_ops.supports_latent(*pools(jnp.bfloat16, keys=(4, 128)))
    with pytest.raises(ValueError, match="cannot walk"):
        paged_ops.latent_paged_attention(
            jnp.zeros((1, 4, 512)), jnp.zeros((1, 4, 64)),
            jnp.zeros((9, 16, 512), jnp.float8_e4m3fn),
            jnp.zeros((9, 8, 128), jnp.float8_e4m3fn),
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
            scale=1.0)
