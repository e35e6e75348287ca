"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode,
tile-skipping (masked) variants on adversarial occupancy patterns, and the
block-divisibility guard on the raw kernel entry points."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.bool_mm import bool_mm as raw_bool_mm
from repro.kernels.count_mm import count_mm as raw_count_mm
from repro.kernels.minplus_mm import minplus_mm as raw_minplus_mm

RNG = np.random.default_rng(0)


def _tile_occ(mat, tile, identity_inf):
    """Tile-occupancy grid of a matrix: nonzero iff tile has non-identity."""
    k, n = mat.shape
    ntr, ntc = -(-k // tile), -(-n // tile)
    pad = np.full((ntr * tile, ntc * tile),
                  np.inf if identity_inf else 0.0, np.float32)
    pad[:k, :n] = mat
    blocks = pad.reshape(ntr, tile, ntc, tile)
    nonid = np.isfinite(blocks) if identity_inf else blocks != 0
    return jnp.asarray(nonid.any(axis=(1, 3)).astype(np.int32))


def _sparse_tiled(k, n, tile, density, identity_inf, rng=RNG):
    """Matrix whose non-identity entries live in a random subset of tiles —
    the adversarial occupancy patterns the skipping must survive."""
    ident = np.inf if identity_inf else 0.0
    mat = np.full((k, n), ident, np.float32)
    ntr, ntc = -(-k // tile), -(-n // tile)
    for i in range(ntr):
        for j in range(ntc):
            if rng.random() < density:
                r0, c0 = i * tile, j * tile
                blk = rng.random((min(tile, k - r0), min(tile, n - c0)))
                vals = np.where(blk < 0.3, blk.astype(np.float32), ident)
                if identity_inf:
                    mat[r0:r0 + blk.shape[0], c0:c0 + blk.shape[1]] = vals
                else:
                    mat[r0:r0 + blk.shape[0], c0:c0 + blk.shape[1]] = (
                        vals != ident).astype(np.float32)
    return mat


@pytest.mark.parametrize("s,k,n", [(128, 128, 128), (70, 200, 130),
                                   (1, 512, 64), (256, 64, 256)])
def test_bool_mm_shapes(s, k, n):
    f = (RNG.random((s, k)) < 0.15).astype(np.float32)
    a = (RNG.random((k, n)) < 0.08).astype(np.float32)
    out = np.asarray(ops.bool_mm(jnp.asarray(f), jnp.asarray(a)))
    exp = np.asarray(ref.bool_mm_ref(jnp.asarray(f), jnp.asarray(a)))
    assert np.array_equal(out, exp)


def test_bool_mm_block_sweep():
    f = (RNG.random((96, 160)) < 0.2).astype(np.float32)
    a = (RNG.random((160, 96)) < 0.2).astype(np.float32)
    exp = np.asarray(ref.bool_mm_ref(jnp.asarray(f), jnp.asarray(a)))
    for bm, bn, bk in [(32, 32, 32), (96, 96, 160), (64, 32, 80)]:
        out = np.asarray(ops.bool_mm(jnp.asarray(f), jnp.asarray(a),
                                     bm=bm, bn=bn, bk=bk))
        assert np.array_equal(out, exp), (bm, bn, bk)


@pytest.mark.parametrize("s,k,n", [(64, 64, 64), (50, 90, 70), (1, 128, 30)])
def test_minplus_shapes(s, k, n):
    d = RNG.random((s, k)).astype(np.float32)
    d[RNG.random((s, k)) < 0.3] = np.inf
    w = RNG.random((k, n)).astype(np.float32)
    w[RNG.random((k, n)) < 0.5] = np.inf
    out = np.asarray(ops.minplus_mm(jnp.asarray(d), jnp.asarray(w)))
    exp = np.asarray(ref.minplus_mm_ref(jnp.asarray(d), jnp.asarray(w)))
    assert np.allclose(out, exp, equal_nan=True)


def test_minplus_all_inf():
    d = np.full((16, 32), np.inf, np.float32)
    w = RNG.random((32, 16)).astype(np.float32)
    out = np.asarray(ops.minplus_mm(jnp.asarray(d), jnp.asarray(w)))
    assert np.isinf(out).all()


@pytest.mark.parametrize("s,k,n", [(128, 128, 128), (70, 200, 130),
                                   (1, 512, 64)])
def test_count_mm_shapes(s, k, n):
    f = (RNG.random((s, k)) * 4).astype(np.int32).astype(np.float32)
    a = (RNG.random((k, n)) < 0.1).astype(np.float32)
    out = np.asarray(ops.count_mm(jnp.asarray(f), jnp.asarray(a)))
    exp = np.asarray(ref.count_mm_ref(jnp.asarray(f), jnp.asarray(a)))
    assert np.array_equal(out, exp)  # integer counts: exact


# ----------------------- tile-skipping (masked) path -----------------------

@pytest.mark.parametrize("s,k,n,tile,density", [
    (64, 256, 192, 64, 0.3),    # block-multiple shapes
    (70, 200, 130, 64, 0.25),   # non-128-multiple everything
    (33, 513, 129, 128, 0.2),   # off-by-one shapes, coarse tiles
    (16, 96, 96, 16, 0.0),      # fully empty adjacency
    (16, 96, 96, 16, 1.0),      # fully dense occupancy (no skipping wins)
])
def test_masked_kernels_match_dense_oracles(s, k, n, tile, density):
    rng = np.random.default_rng(hash((s, k, n, tile)) % 2**32)
    # min-plus: identity is +inf
    w = _sparse_tiled(k, n, tile, density, identity_inf=True, rng=rng)
    d = rng.random((s, k)).astype(np.float32)
    d[rng.random((s, k)) < 0.5] = np.inf
    wmask = _tile_occ(w, tile, identity_inf=True)
    exp = np.asarray(ref.minplus_mm_ref(jnp.asarray(d), jnp.asarray(w)))
    got = np.asarray(ops.minplus_mm(jnp.asarray(d), jnp.asarray(w),
                                    amask=wmask, tile=tile))
    assert np.allclose(got, exp, equal_nan=True)
    # bool / count: identity is 0
    a = _sparse_tiled(k, n, tile, density, identity_inf=False, rng=rng)
    f = (rng.random((s, k)) < 0.15).astype(np.float32)
    amask = _tile_occ(a, tile, identity_inf=False)
    exp_b = np.asarray(ref.bool_mm_ref(jnp.asarray(f), jnp.asarray(a)))
    got_b = np.asarray(ops.bool_mm(jnp.asarray(f), jnp.asarray(a),
                                   amask=amask, tile=tile))
    assert np.array_equal(got_b, exp_b)
    exp_c = np.asarray(ref.count_mm_ref(jnp.asarray(f), jnp.asarray(a)))
    got_c = np.asarray(ops.count_mm(jnp.asarray(f), jnp.asarray(a),
                                    amask=amask, tile=tile))
    assert np.array_equal(got_c, exp_c)


def test_masked_kernels_adversarial_single_tile():
    """One live tile in a far corner: everything else must be skipped yet
    the corner's contribution must survive."""
    tile, k, n, s = 32, 160, 160, 48
    w = np.full((k, n), np.inf, np.float32)
    w[128:160, 128:160] = 1.0  # bottom-right tile only
    d = np.full((s, k), np.inf, np.float32)
    d[:, 130] = 2.0  # reaches into the live k range
    wmask = _tile_occ(w, tile, identity_inf=True)
    assert int(np.asarray(wmask).sum()) == 1
    exp = np.asarray(ref.minplus_mm_ref(jnp.asarray(d), jnp.asarray(w)))
    got = np.asarray(ops.minplus_mm(jnp.asarray(d), jnp.asarray(w),
                                    amask=wmask, tile=tile))
    assert np.allclose(got, exp, equal_nan=True)
    assert np.isfinite(got[:, 128:160]).all()


def test_masked_jnp_fallback_matches_kernel():
    """semiring.* masked fallbacks == masked kernels == dense oracles."""
    from repro.core import semiring
    rng = np.random.default_rng(9)
    tile, k, n, s = 16, 96, 80, 24
    w = _sparse_tiled(k, n, tile, 0.3, identity_inf=True, rng=rng)
    d = rng.random((s, k)).astype(np.float32)
    wmask = _tile_occ(w, tile, identity_inf=True)
    exp = np.asarray(ref.minplus_mm_ref(jnp.asarray(d), jnp.asarray(w)))
    for uk in (False, True):
        got = np.asarray(semiring.minplus_mm(
            jnp.asarray(d), jnp.asarray(w), use_kernel=uk, amask=wmask,
            tile=tile))
        assert np.allclose(got, exp, equal_nan=True), uk
    a = _sparse_tiled(k, n, tile, 0.3, identity_inf=False, rng=rng)
    f = (rng.random((s, k)) < 0.2).astype(np.float32)
    amask = _tile_occ(a, tile, identity_inf=False)
    exp_b = np.asarray(ref.bool_mm_ref(jnp.asarray(f), jnp.asarray(a)))
    exp_c = np.asarray(ref.count_mm_ref(jnp.asarray(f), jnp.asarray(a)))
    for uk in (False, True):
        got_b = np.asarray(semiring.bool_mm(
            jnp.asarray(f), jnp.asarray(a), use_kernel=uk, amask=amask,
            tile=tile))
        got_c = np.asarray(semiring.count_mm(
            jnp.asarray(f), jnp.asarray(a), use_kernel=uk, amask=amask,
            tile=tile))
        assert np.array_equal(got_b, exp_b), uk
        assert np.array_equal(got_c, exp_c), uk


# ---------------------- raw-kernel truncation guard ------------------------

@pytest.mark.parametrize("raw", [raw_bool_mm, raw_minplus_mm, raw_count_mm])
def test_raw_kernels_reject_truncating_shapes(raw):
    """grid = shape // block used to silently drop trailing rows/columns;
    now a direct call with non-dividing shapes raises."""
    x = jnp.asarray(np.full((130, 64), 1.0, np.float32))
    y = jnp.asarray(np.full((64, 64), 1.0, np.float32))
    with pytest.raises(ValueError, match="truncation"):
        raw(x, y, bm=128, bn=64, bk=64)
    # dividing shapes still work
    out = raw(x[:128], y, bm=128, bn=64, bk=64)
    assert out.shape == (128, 64)


def test_raw_kernels_default_interpret_from_backend():
    """The raw kernels must not hardcode interpret=True: the default comes
    from backend detection at trace time (interpret off on real TPU)."""
    import inspect
    from repro.kernels import backend
    for fn in (raw_bool_mm, raw_minplus_mm, raw_count_mm):
        sig = inspect.signature(fn.__wrapped__)
        assert sig.parameters["interpret"].default is None
    assert backend.resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert backend.resolve_interpret(False) is False


@pytest.mark.parametrize("b,hq,hkv,sq,skv,d", [
    (1, 4, 4, 32, 32, 16),     # MHA square
    (2, 4, 2, 37, 53, 16),     # GQA ragged
    (1, 8, 1, 16, 64, 32),     # MQA decode-ish (ends aligned)
    (2, 2, 2, 1, 40, 16),      # single-query decode
])
def test_flash_attention_causal(b, hq, hkv, sq, skv, d):
    q = RNG.standard_normal((b, hq, sq, d)).astype(np.float32)
    k = RNG.standard_normal((b, hkv, skv, d)).astype(np.float32)
    v = RNG.standard_normal((b, hkv, skv, d)).astype(np.float32)
    out = ops.flash_attention(*map(jnp.asarray, (q, k, v)), bq=16, bk=16)
    exp = ref.flash_attention_ref(*map(jnp.asarray, (q, k, v)))
    assert np.max(np.abs(np.asarray(out) - np.asarray(exp))) < 3e-5


def test_flash_attention_noncausal():
    q = RNG.standard_normal((1, 2, 24, 16)).astype(np.float32)
    k = RNG.standard_normal((1, 2, 40, 16)).astype(np.float32)
    v = RNG.standard_normal((1, 2, 40, 16)).astype(np.float32)
    out = ops.flash_attention(*map(jnp.asarray, (q, k, v)), causal=False,
                              bq=16, bk=16)
    exp = ref.flash_attention_ref(*map(jnp.asarray, (q, k, v)), causal=False)
    assert np.max(np.abs(np.asarray(out) - np.asarray(exp))) < 3e-5


def test_flash_attention_window():
    q = RNG.standard_normal((1, 2, 48, 16)).astype(np.float32)
    k = RNG.standard_normal((1, 2, 48, 16)).astype(np.float32)
    v = RNG.standard_normal((1, 2, 48, 16)).astype(np.float32)
    out = ops.flash_attention(*map(jnp.asarray, (q, k, v)), window=8,
                              bq=16, bk=16)
    # windowed oracle
    lg = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(16)
    i = np.arange(48)[:, None]
    j = np.arange(48)[None, :]
    m = (j <= i) & (j > i - 8)
    lg = np.where(m[None, None], lg, -np.inf)
    p = np.exp(lg - lg.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    exp = np.einsum("bhqk,bhkd->bhqd", p, v)
    assert np.max(np.abs(np.asarray(out) - exp)) < 3e-5


def test_flash_attention_bf16():
    q = RNG.standard_normal((1, 2, 32, 16)).astype(np.float32)
    k = RNG.standard_normal((1, 2, 32, 16)).astype(np.float32)
    v = RNG.standard_normal((1, 2, 32, 16)).astype(np.float32)
    qb, kb, vb = (jnp.asarray(x, jnp.bfloat16) for x in (q, k, v))
    out = ops.flash_attention(qb, kb, vb, bq=16, bk=16)
    exp = ref.flash_attention_ref(qb, kb, vb)
    assert out.dtype == jnp.bfloat16
    assert np.max(np.abs(np.asarray(out, np.float32)
                         - np.asarray(exp, np.float32))) < 3e-2


# ---------------- shift merge (the commit's table shift) ----------------

@pytest.mark.parametrize("n,n_pos,n_app", [
    (256, 16, 16),                 # one short block, padded to the halo
    (3 * 256 * 128 + 1024, 32, 29),  # three full blocks and a partial one
    (5000, 1500, 1400),            # a shift past one row of halo rows
    (4096, 8, 0),                  # no appends: the table comes back whole
], ids=["short", "partial-block", "wide-halo", "no-appends"])
def test_shift_merge_moves_each_slot_past_its_appends(n, n_pos, n_app):
    """``spread`` moves slot j to ``j + #{i: pos[i] <= j}`` (dropping what
    passes the end) and leaves only the appends' slots unwritten."""
    from repro.kernels.shift_merge import spread

    rng = np.random.default_rng(n)
    pos = np.sort(np.concatenate([
        rng.integers(0, n + 1, n_app - min(n_app, 3)),
        [0, n // 2, n // 2][:min(n_app, 3)],
        np.full(n_pos - n_app, n)])).astype(np.int32)
    cols = [rng.integers(-2**31, 2**31 - 1, n, dtype=np.int64).astype(np.int32),
            rng.integers(0, 99, n).astype(np.int32),
            rng.random(n).astype(np.float32)]
    got = spread([jnp.asarray(c) for c in cols], jnp.asarray(pos))
    dest = np.arange(n) + np.searchsorted(pos, np.arange(n), side="right")
    keep = dest < n
    holes = np.ones(n, bool)
    holes[dest[keep]] = False
    assert holes.sum() == min(n_app, n) - (pos[:n_app] + np.arange(n_app)
                                           >= n).sum()
    for c, g in zip(cols, got):
        assert g.dtype == c.dtype
        assert np.array_equal(np.asarray(g)[dest[keep]], c[keep])
