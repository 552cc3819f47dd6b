"""Per-kernel allclose vs pure-jnp oracles: shape & dtype sweeps
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(42)


# --------------------------------------------------------------------------
# transpose (paper §IV.C)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(256, 384), (100, 130), (8, 4096),
                                   (31, 7), (1, 1), (129, 257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_transpose2d(shape, dtype):
    from repro.kernels.transpose.ops import transpose2d
    from repro.kernels.transpose.ref import transpose2d_ref
    x = jax.random.normal(KEY, shape, jnp.float32).astype(dtype)
    np.testing.assert_array_equal(np.asarray(transpose2d(x)),
                                  np.asarray(transpose2d_ref(x)))


@pytest.mark.parametrize("shape", [(3, 50, 70), (2, 128, 128), (5, 17, 9)])
def test_transpose2d_batched(shape):
    from repro.kernels.transpose.ops import transpose2d_batched
    x = jax.random.normal(KEY, shape)
    np.testing.assert_array_equal(np.asarray(transpose2d_batched(x)),
                                  np.swapaxes(np.asarray(x), 1, 2))


def test_transpose_block_alignment():
    """Block picker honors dtype-native tiles (the float2 analogue)."""
    from repro.kernels.transpose.ops import pick_blocks
    bm32, _ = pick_blocks(4096, 4096, jnp.float32)
    bm16, _ = pick_blocks(4096, 4096, jnp.bfloat16)
    assert bm32 % 8 == 0 and bm16 % 16 == 0


# --------------------------------------------------------------------------
# fused softmax (paper §V.B)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,c", [(128, 10), (64, 1000), (37, 513), (1, 10000),
                                 (128, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_softmax_fused(n, c, dtype):
    from repro.kernels.softmax.ops import softmax
    from repro.kernels.softmax.ref import softmax_5step_ref, softmax_ref
    x = (jax.random.normal(KEY, (n, c)) * 5).astype(dtype)
    got = np.asarray(softmax(x), np.float32)
    np.testing.assert_allclose(got, np.asarray(softmax_ref(x), np.float32),
                               atol=2e-3 if dtype == jnp.bfloat16 else 1e-6)
    # the fused kernel equals the paper's literal 5-step pipeline
    np.testing.assert_allclose(
        got, np.asarray(softmax_5step_ref(x), np.float32),
        atol=2e-3 if dtype == jnp.bfloat16 else 1e-6)
    # bf16 probabilities round to ~3 decimal digits; sums drift O(1e-2)
    np.testing.assert_allclose(got.sum(-1), 1.0,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-3)


@pytest.mark.parametrize("n,c", [(128, 10), (64, 1000)])
def test_softmax_xent(n, c):
    from repro.kernels.softmax.ops import softmax_xent
    from repro.kernels.softmax.ref import softmax_xent_ref
    x = jax.random.normal(KEY, (n, c)) * 3
    lab = jax.random.randint(KEY, (n,), 0, c)
    np.testing.assert_allclose(np.asarray(softmax_xent(x, lab)),
                               np.asarray(softmax_xent_ref(x, lab)), rtol=1e-5)


# --------------------------------------------------------------------------
# pooling (paper §V.A) — window reuse + both layouts
# --------------------------------------------------------------------------
POOL_CASES = [(16, 28, 28, 128, 2, 2, "max"), (64, 24, 24, 128, 3, 2, "avg"),
              pytest.param(96, 55, 55, 64, 3, 2, "max",
                           marks=pytest.mark.slow),   # paper-size PL5/PL8
              (16, 14, 14, 32, 2, 2, "avg"),
              (8, 13, 13, 32, 3, 2, "max")]


@pytest.mark.parametrize("C,H,W,N,F,S,op", POOL_CASES)
def test_pool_chwn(C, H, W, N, F, S, op):
    from repro.kernels.pool.ops import pool_chwn
    from repro.kernels.pool.ref import pool_ref
    x = jax.random.normal(KEY, (C, H, W, N))
    np.testing.assert_allclose(np.asarray(pool_chwn(x, F, S, op)),
                               np.asarray(pool_ref(x, F, S, op, "CHWN")),
                               atol=1e-5)


@pytest.mark.parametrize("C,H,W,N,F,S,op", POOL_CASES[:3])
def test_pool_nchw(C, H, W, N, F, S, op):
    from repro.kernels.pool.ops import pool_nchw
    from repro.kernels.pool.ref import pool_ref
    x = jax.random.normal(KEY, (N, C, H, W))
    np.testing.assert_allclose(np.asarray(pool_nchw(x, F, S, op)),
                               np.asarray(pool_ref(x, F, S, op, "NCHW")),
                               atol=1e-5)


@pytest.mark.parametrize("N,nt,lanes,want", [
    (128, 8, 100, 8),       # CHWN_NT binds
    (128, 4, 100, 4),       # the caller's nt binds
    (3, 8, 100, 2),         # the batch binds: largest power of two <= 3
    (128, 8, 576, 2),       # SLAB_LANES binds (the 24x24 POOL_CASES slab)
    (128, 128, 729, 2),     # ... and for a 27x27 slab, nt above CHWN_NT
    (128, 8, 3000, 1),      # one sample overflows alone: still 1
    (1, 8, 10, 1),
])
def test_group_tile(N, nt, lanes, want):
    """Samples per CHWN slab: the largest power of two within min(nt,
    CHWN_NT, N) whose slab of ``lanes`` per sample fits SLAB_LANES; the NCHW
    engine always runs one sample per slab."""
    from repro.kernels import flat
    g = flat.group_tile(N, "CHWN", nt, lanes)
    assert g == want
    cap = min(nt, flat.CHWN_NT, N)
    assert g & (g - 1) == 0 and g <= max(1, cap)
    assert g == 1 or g * lanes <= flat.SLAB_LANES
    assert 2 * g > cap or 2 * g * lanes > flat.SLAB_LANES    # maximal
    assert flat.group_tile(N, "NCHW", nt, lanes) == 1


# --------------------------------------------------------------------------
# matmul
# --------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(256, 256, 256), (100, 300, 50),
                                   (8, 1024, 128), (1, 7, 3)])
def test_matmul(m, k, n):
    from repro.kernels.matmul.ops import matmul
    from repro.kernels.matmul.ref import matmul_ref
    x = jax.random.normal(KEY, (m, k))
    y = jax.random.normal(jax.random.PRNGKey(7), (k, n))
    np.testing.assert_allclose(np.asarray(matmul(x, y)),
                               np.asarray(matmul_ref(x, y)),
                               rtol=2e-5, atol=2e-4)


# --------------------------------------------------------------------------
# direct conv (CHWN) + im2col (NCHW) + FFT
# --------------------------------------------------------------------------
CONV_CASES = [(1, 28, 28, 32, 5, 16, 1, 0), (16, 14, 14, 64, 5, 16, 1, 2),
              (3, 32, 32, 32, 3, 8, 2, 0), (8, 13, 13, 32, 3, 16, 1, 1)]


@pytest.mark.parametrize("Ci,H,W,N,F,Co,S,pad", CONV_CASES)
def test_conv_direct_chwn(Ci, H, W, N, F, Co, S, pad):
    from repro.kernels.conv.ops import conv_direct_chwn
    from repro.kernels.conv.ref import conv_chwn_ref
    x = jax.random.normal(KEY, (Ci, H, W, N))
    w = jax.random.normal(jax.random.PRNGKey(3), (Ci, F, F, Co)) * 0.1
    np.testing.assert_allclose(
        np.asarray(conv_direct_chwn(x, w, stride=S, pad=pad)),
        np.asarray(conv_chwn_ref(x, w, stride=S, pad=pad)),
        rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("Ci,H,W,N,F,Co,S,pad", CONV_CASES)
def test_conv_im2col_and_fft(Ci, H, W, N, F, Co, S, pad):
    from repro.kernels.conv.ops import conv_fft_nchw, conv_im2col_nchw
    from repro.kernels.conv.ref import conv_nchw_ref
    x = jax.random.normal(KEY, (N, Ci, H, W))
    w = jax.random.normal(jax.random.PRNGKey(3), (Co, Ci, F, F)) * 0.1
    ref = np.asarray(conv_nchw_ref(x, w, stride=S, pad=pad))
    np.testing.assert_allclose(
        np.asarray(conv_im2col_nchw(x, w, stride=S, pad=pad)), ref,
        rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(
        np.asarray(conv_fft_nchw(x, w, stride=S, pad=pad)), ref,
        rtol=1e-3, atol=1e-2)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------
@pytest.mark.parametrize("bh,s,d,causal", [(4, 256, 64, True),
                                           (2, 128, 32, False),
                                           (6, 512, 128, True)])
def test_flash_attention(bh, s, d, causal):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    q = jax.random.normal(KEY, (bh, s, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, s, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, s, d))
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=causal, bq=64, bk=64)),
        np.asarray(attention_ref(q, k, v, causal=causal)),
        rtol=1e-4, atol=1e-4)


def test_flash_attention_4d():
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    q = jax.random.normal(KEY, (2, 3, 128, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 128, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 128, 64))
    got = flash_attention(q, k, v, causal=True)
    ref = attention_ref(q.reshape(6, 128, 64), k.reshape(6, 128, 64),
                        v.reshape(6, 128, 64), causal=True).reshape(2, 3, 128, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# fused cross entropy (streamed unembed)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("t,v,d,cap", [(64, 1000, 128, None),
                                       (128, 513, 64, None),
                                       (32, 2000, 96, 30.0),
                                       (16, 128, 32, None)])
def test_fused_xent(t, v, d, cap):
    from repro.kernels.crossentropy.ops import fused_xent
    from repro.kernels.crossentropy.ref import xent_ref
    h = jax.random.normal(KEY, (t, d))
    table = jax.random.normal(jax.random.PRNGKey(1), (v, d)) * 0.05
    lab = jax.random.randint(KEY, (t,), 0, v)
    np.testing.assert_allclose(
        np.asarray(fused_xent(h, table, lab, bv=256, softcap=cap)),
        np.asarray(xent_ref(h, table, lab, softcap=cap)),
        rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# fused execution engine (DESIGN.md §5): conv epilogues + layout-fused I/O
# --------------------------------------------------------------------------
def _fused_chwn_ref(x, w, S, pad, bias, relu, pool):
    """Unfused oracle: conv -> (+bias) -> (relu) -> (pool), all in CHWN."""
    from repro.kernels.conv.ref import conv_chwn_ref
    from repro.kernels.pool.ref import pool_ref
    y = conv_chwn_ref(x, w, stride=S, pad=pad).astype(jnp.float32)
    if bias is not None:
        y = y + bias[:, None, None, None]
    if relu:
        y = jnp.maximum(y, 0.0)
    if pool is not None:
        y = pool_ref(y, pool[0], pool[1], pool[2], "CHWN")
    return y


FUSED_CASES = [  # Ci, H, W, N, F, Co, S, pad, pool
    (3, 16, 16, 8, 3, 16, 1, 1, (2, 2, "max")),
    (3, 16, 16, 8, 3, 16, 1, 1, (3, 2, "max")),     # overlapping windows
    (16, 14, 14, 4, 5, 32, 2, 2, (2, 2, "avg")),    # stride-2 conv
    (8, 13, 13, 6, 3, 16, 1, 0, None),              # bias+relu only
]


@pytest.mark.parametrize("Ci,H,W,N,F,Co,S,pad,pool", FUSED_CASES)
@pytest.mark.parametrize("dst", ["CHWN", "NCHW"])
def test_conv_chwn_fused_epilogue(Ci, H, W, N, F, Co, S, pad, pool, dst):
    """conv+bias+relu(+pool) as ONE kernel == the unfused chain, and the
    dst_layout write equals apply_transform after the chain."""
    from repro.kernels.conv.ops import conv_direct_chwn
    x = jax.random.normal(KEY, (Ci, H, W, N))
    w = jax.random.normal(jax.random.PRNGKey(3), (Ci, F, F, Co)) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(5), (Co,)) * 0.5
    ref = _fused_chwn_ref(x, w, S, pad, b, True, pool)
    got = conv_direct_chwn(x, w, stride=S, pad=pad, bias=b, relu=True,
                           pool=pool, dst_layout=dst)
    if dst == "NCHW":
        got = jnp.transpose(got, (1, 2, 3, 0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("Ci,H,W,N,F,Co,S,pad,pool", FUSED_CASES[:2])
def test_conv_chwn_src_layout_fusion(Ci, H, W, N, F, Co, S, pad, pool):
    """The CHWN kernel consumes NCHW input directly (the folded transform
    the network pays at its entry)."""
    from repro.kernels.conv.ops import conv_direct_chwn
    x = jax.random.normal(KEY, (Ci, H, W, N))
    w = jax.random.normal(jax.random.PRNGKey(3), (Ci, F, F, Co)) * 0.1
    ref = _fused_chwn_ref(x, w, S, pad, None, True, pool)
    got = conv_direct_chwn(jnp.transpose(x, (3, 0, 1, 2)), w, stride=S,
                           pad=pad, relu=True, pool=pool, src_layout="NCHW")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("Ci,H,W,N,F,Co,S,pad,pool", FUSED_CASES)
@pytest.mark.parametrize("dst", ["NCHW", "CHWN"])
def test_conv_nchw_native_fused(Ci, H, W, N, F, Co, S, pad, pool, dst):
    """The native im2col-MM NCHW Pallas conv (no XLA expansion) with the
    same epilogue protocol and layout-fused output."""
    from repro.kernels.conv.ops import conv_im2col_nchw_fused
    from repro.kernels.conv.ref import conv_nchw_ref
    from repro.kernels.pool.ref import pool_ref
    x = jax.random.normal(KEY, (N, Ci, H, W))
    w = jax.random.normal(jax.random.PRNGKey(3), (Co, Ci, F, F)) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(5), (Co,)) * 0.5
    ref = conv_nchw_ref(x, w, stride=S, pad=pad).astype(jnp.float32)
    ref = jnp.maximum(ref + b[None, :, None, None], 0.0)
    if pool is not None:
        ref = pool_ref(ref, pool[0], pool[1], pool[2], "NCHW")
    got = conv_im2col_nchw_fused(x, w, stride=S, pad=pad, bias=b, relu=True,
                                 pool=pool, dst_layout=dst)
    if dst == "CHWN":
        got = jnp.transpose(got, (3, 0, 1, 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_conv_nchw_native_matches_im2col_baseline():
    """Plain native NCHW conv == the seed's XLA-expansion im2col path."""
    from repro.kernels.conv.ops import conv_im2col_nchw, conv_im2col_nchw_fused
    x = jax.random.normal(KEY, (4, 8, 13, 13))
    w = jax.random.normal(jax.random.PRNGKey(3), (16, 8, 3, 3)) * 0.1
    np.testing.assert_allclose(
        np.asarray(conv_im2col_nchw_fused(x, w, stride=2, pad=1)),
        np.asarray(conv_im2col_nchw(x, w, stride=2, pad=1)),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("C,H,W,N,F,S,op", POOL_CASES[:3])
def test_pool_dst_layout_fusion(C, H, W, N, F, S, op):
    """Pool kernels write directly in the consumer's layout: the fused
    output equals apply_transform after the unfused pool."""
    from repro.kernels.pool.ops import pool_chwn, pool_nchw
    from repro.kernels.pool.ref import pool_ref
    x = jax.random.normal(KEY, (C, H, W, N))
    got = pool_chwn(x, F, S, op, dst_layout="NCHW")
    ref = jnp.transpose(pool_ref(x, F, S, op, "CHWN"), (3, 0, 1, 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    xn = jax.random.normal(KEY, (N, C, H, W))
    got = pool_nchw(xn, F, S, op, dst_layout="CHWN")
    ref = jnp.transpose(pool_ref(xn, F, S, op, "NCHW"), (1, 2, 3, 0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_pool_tiles_block_gate():
    """The pool epilogue is only fused when its windows tile the conv-output
    row block (whole-height blocks always qualify)."""
    from repro.kernels.conv.conv import pool_tiles_block
    assert pool_tiles_block(4, 3, 2, 2)          # aligned, non-overlapping
    assert not pool_tiles_block(4, 3, 3, 2)      # overlapping, crosses seams
    assert pool_tiles_block(12, 1, 3, 2)         # one block: always tiles
    assert not pool_tiles_block(2, 3, 3, 2)      # window taller than block


@pytest.mark.parametrize("Ci,H,Co,F,S,pad", [
    (1, 7, 8, 5, 1, 0),      # Ho=3 < ceil((F-S)/S)=4: whole-height fallback
    (3, 9, 8, 7, 1, 0),      # Ho=3 < 6
    (2, 6, 4, 5, 2, 1),      # strided small-Ho case
])
def test_conv_small_output_height_halo(Ci, H, Co, F, S, pad):
    """Output heights below ceil((F-S)/S) force bho < min_bho; the widened
    input row block must still cover the window span (regression: the two
    stitched bho*S blocks were too short and the tap loop crashed)."""
    from repro.kernels.conv.ops import conv_direct_chwn, conv_im2col_nchw_fused
    from repro.kernels.conv.ref import conv_chwn_ref, conv_nchw_ref
    x = jax.random.normal(KEY, (2, Ci, H, H))
    w = jax.random.normal(jax.random.PRNGKey(3), (Co, Ci, F, F)) * 0.1
    np.testing.assert_allclose(
        np.asarray(conv_im2col_nchw_fused(x, w, stride=S, pad=pad)),
        np.asarray(conv_nchw_ref(x, w, stride=S, pad=pad)),
        rtol=1e-4, atol=1e-4)
    xc = jnp.transpose(x, (1, 2, 3, 0))
    wc = jnp.transpose(w, (1, 2, 3, 0))
    np.testing.assert_allclose(
        np.asarray(conv_direct_chwn(xc, wc, stride=S, pad=pad)),
        np.asarray(conv_chwn_ref(xc, wc, stride=S, pad=pad)),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("Ci,H,F,Co,S,pad", [
    (3, 8, 1, 4, 1, 0),      # 1x1 conv
    (3, 8, 2, 4, 2, 0),      # patchify: F == S
    (3, 8, 1, 4, 2, 0),      # F < S
    (3, 9, 3, 4, 3, 0),
])
def test_conv_small_filter_no_spurious_rows(Ci, H, F, Co, S, pad):
    """F <= S convs: the halo row padding must not leak extra output row
    blocks (regression: the engines recomputed Ho from the padded input and
    the wrappers only sliced channels, returning garbage trailing rows)."""
    from repro.kernels.conv.ops import conv_direct_chwn, conv_im2col_nchw_fused
    from repro.kernels.conv.ref import conv_chwn_ref, conv_nchw_ref
    x = jax.random.normal(KEY, (2, Ci, H, H))
    w = jax.random.normal(jax.random.PRNGKey(3), (Co, Ci, F, F)) * 0.1
    ref = conv_nchw_ref(x, w, stride=S, pad=pad)
    got = conv_im2col_nchw_fused(x, w, stride=S, pad=pad)
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    xc, wc = jnp.transpose(x, (1, 2, 3, 0)), jnp.transpose(w, (1, 2, 3, 0))
    refc = conv_chwn_ref(xc, wc, stride=S, pad=pad)
    gotc = conv_direct_chwn(xc, wc, stride=S, pad=pad)
    assert gotc.shape == refc.shape
    np.testing.assert_allclose(np.asarray(gotc), np.asarray(refc),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("Ci,Co", [(48, 16), (32, 200), (48, 200)])
def test_conv_channels_not_tile_divisible(Ci, Co):
    """Ci/Co that don't divide the channel tiles (32/128) are zero-padded,
    not silently truncated (regression: grid floor-division dropped them)."""
    from repro.kernels.conv.ops import conv_direct_chwn, conv_im2col_nchw_fused
    from repro.kernels.conv.ref import conv_chwn_ref, conv_nchw_ref
    x = jax.random.normal(KEY, (2, Ci, 8, 8))
    w = jax.random.normal(jax.random.PRNGKey(3), (Co, Ci, 3, 3)) * 0.1
    np.testing.assert_allclose(
        np.asarray(conv_im2col_nchw_fused(x, w, stride=1, pad=1)),
        np.asarray(conv_nchw_ref(x, w, stride=1, pad=1)),
        rtol=1e-4, atol=1e-4)
    xc = jnp.transpose(x, (1, 2, 3, 0))
    wc = jnp.transpose(w, (1, 2, 3, 0))
    np.testing.assert_allclose(
        np.asarray(conv_direct_chwn(xc, wc, stride=1, pad=1)),
        np.asarray(conv_chwn_ref(xc, wc, stride=1, pad=1)),
        rtol=1e-4, atol=1e-4)
