"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention, mha
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.fused_mlp.ops import fused_mlp
from repro.kernels.fused_mlp.ref import fused_mlp_layer_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- fused_mlp
@pytest.mark.parametrize("m,k,n", [(1, 64, 32), (37, 300, 129),
                                   (128, 512, 256), (200, 1000, 513)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["leaky_relu", "relu", "tanh", "linear"])
def test_fused_mlp_matches_ref(m, k, n, dtype, act):
    key = jax.random.PRNGKey(m * 7 + n)
    x = jax.random.normal(key, (m, k), dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
         * 0.05).astype(dtype)
    b = jax.random.normal(jax.random.fold_in(key, 2), (n,), dtype)
    out = fused_mlp(x, w, b, activation=act)
    ref = fused_mlp_layer_ref(x, w, b, activation=act)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_fused_mlp_dfp_sizes():
    """The paper's exact state-module sizes (11410 -> 4000) in bf16."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 11410), jnp.bfloat16)
    w = (jax.random.normal(key, (11410, 4000), jnp.float32) * 0.01
         ).astype(jnp.bfloat16)
    b = jnp.zeros((4000,), jnp.bfloat16)
    out = fused_mlp(x, w, b)
    ref = fused_mlp_layer_ref(x, w, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=5e-2,
                               atol=5e-2)


# ------------------------------------------------- fused_mlp custom VJP
def _grads(f, x, w, b, n):
    """d/d(x,w,b) of a fixed scalar projection of f's output."""
    ct = jnp.sin(jnp.arange(n) * 0.37)
    return jax.grad(lambda x, w, b: (f(x, w, b) * ct).sum(), (0, 1, 2))(
        x, w, b)


# Real DFP layer shapes: paper-scale state module rows (4000->1000->512)
# and the packed decision batches the rollout engine actually emits —
# including odd lane counts whose M is no multiple of any block.
DFP_GRAD_SHAPES = [(1, 1000, 512), (3, 512, 128), (5, 4000, 1000),
                   (37, 300, 129)]


@pytest.mark.parametrize("m,k,n", DFP_GRAD_SHAPES)
@pytest.mark.parametrize("act", ["leaky_relu", "relu", "tanh", "linear"])
def test_fused_mlp_grad_matches_ref(m, k, n, act):
    key = jax.random.PRNGKey(m * 31 + n)
    x = jax.random.normal(key, (m, k))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n)) * 0.05
    b = jax.random.normal(jax.random.fold_in(key, 2), (n,)) * 0.1
    gk = _grads(lambda x, w, b: fused_mlp(x, w, b, activation=act),
                x, w, b, n)
    gr = _grads(lambda x, w, b: fused_mlp_layer_ref(x, w, b, activation=act),
                x, w, b, n)
    for got, ref, name in zip(gk, gr, ("dx", "dw", "db")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-3, atol=1e-4, err_msg=name)


def test_fused_mlp_grad_bf16():
    """bf16 fwd+grad stays within bf16 resolution of the f32 oracle."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (5, 256), jnp.bfloat16)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (256, 128),
                           jnp.float32) * 0.05).astype(jnp.bfloat16)
    b = jnp.zeros((128,), jnp.bfloat16)
    gk = _grads(lambda x, w, b: fused_mlp(x, w, b), x, w, b, 128)
    xf, wf, bf = (t.astype(jnp.float32) for t in (x, w, b))
    gr = _grads(lambda x, w, b: fused_mlp_layer_ref(x, w, b), xf, wf, bf, 128)
    for got, ref in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=5e-2, atol=5e-2)


def test_fused_mlp_state_module_chain_grad():
    """Grad parity through the whole fused state-module MLP chain."""
    from repro.kernels.fused_mlp.ops import dfp_state_module
    key = jax.random.PRNGKey(11)
    sizes = [(300, 128), (128, 64)]
    layers = [{"w": jax.random.normal(jax.random.fold_in(key, 2 * i),
                                      (k, n)) * 0.05,
               "b": jax.random.normal(jax.random.fold_in(key, 2 * i + 1),
                                      (n,)) * 0.1}
              for i, (k, n) in enumerate(sizes)]
    x = jax.random.normal(key, (7, 300))

    def ref_chain(x, layers):
        h = x
        for l in layers:
            h = fused_mlp_layer_ref(h, l["w"], l["b"])
        return h

    gk = jax.grad(lambda x, ls: dfp_state_module(x, ls).sum(), (0, 1))(
        x, layers)
    gr = jax.grad(lambda x, ls: ref_chain(x, ls).sum(), (0, 1))(x, layers)
    for got, ref in zip(jax.tree_util.tree_leaves(gk),
                        jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-3, atol=1e-4)


# ------------------------------------------------------------- flash attn
@pytest.mark.parametrize("B,S,H,KV,dh", [
    (1, 128, 2, 2, 64), (2, 200, 4, 2, 64), (1, 384, 8, 1, 128),
    (2, 256, 6, 6, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, S, H, KV, dh, dtype, causal):
    key = jax.random.PRNGKey(S + H)
    q = jax.random.normal(key, (B, S, H, dh), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, dh), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, dh), dtype)
    out = flash_attention(q, k, v, causal=causal)
    kr = jnp.repeat(k, H // KV, 2)
    vr = jnp.repeat(v, H // KV, 2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    kf = kr.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    vf = vr.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    ref = attention_ref(qf, kf, vf, causal=causal).reshape(B, H, S, dh)
    ref = ref.transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_flash_attention_cross_lengths():
    """Sq != Sk (non-causal cross attention path)."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (2, 100, 4, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 260, 4, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 260, 4, 64))
    out = flash_attention(q, k, v, causal=False)
    qf = q.transpose(0, 2, 1, 3).reshape(8, 100, 64)
    kf = k.transpose(0, 2, 1, 3).reshape(8, 260, 64)
    vf = v.transpose(0, 2, 1, 3).reshape(8, 260, 64)
    ref = attention_ref(qf, kf, vf, causal=False).reshape(2, 4, 100, 64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.transpose(0, 2, 1, 3)),
                               rtol=2e-4, atol=2e-4)


# ----------------------------------------------------- masked mha (+ vjp)
# The queue-as-tokens encoder's kernel: non-causal attention over
# variable-length token sets, with a fused Pallas backward.  Shapes are
# the encoder's real ones — S = 1 + queue_cap, which is deliberately odd
# and no multiple of any block size — and the length grids always include
# 0 (an env with an empty queue: a fully-masked tail must output and
# backprop exactly zero, not NaN).

def _mha_case(S, dh, seed=0, BH=8):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (BH, S, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (BH, S, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (BH, S, dh))
    lens = jnp.asarray([0, 1, 3, S // 2, max(S - 1, 1), S, 2, S // 3][:BH],
                       jnp.float32)
    return q, k, v, lens


# S = 1 + Q for queue caps 48 / 128 / 64 (none block-aligned); block 128
# exercises the single-block fast path, 32/64 the multi-block online
# softmax across partially- and fully-masked key blocks.
MHA_SHAPES = [(49, 16, 32), (129, 32, 64), (65, 8, 128)]


@pytest.mark.parametrize("S,dh,block", MHA_SHAPES)
def test_mha_fwd_matches_ref(S, dh, block):
    q, k, v, lens = _mha_case(S, dh, seed=S)
    out = mha(q, k, v, lens, block_q=block, block_k=block, interpret=True)
    ref = attention_ref(q, k, v, causal=False, lengths=lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("S,dh,block", MHA_SHAPES)
def test_mha_vjp_matches_ref(S, dh, block):
    q, k, v, lens = _mha_case(S, dh, seed=S + 1)
    ct = jnp.sin(jnp.arange(S * dh) * 0.13).reshape(1, S, dh)

    def proj(f):
        return jax.grad(lambda q, k, v: (f(q, k, v) * ct).sum(), (0, 1, 2))(
            q, k, v)

    gk = proj(lambda q, k, v: mha(q, k, v, lens, block_q=block,
                                  block_k=block, interpret=True))
    gr = proj(lambda q, k, v: attention_ref(q, k, v, causal=False,
                                            lengths=lens))
    for got, ref, name in zip(gk, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-3, atol=1e-4, err_msg=name)


def test_mha_fully_masked_is_exactly_zero():
    """length 0 everywhere: outputs AND all gradients are exactly 0."""
    q, k, v, _ = _mha_case(33, 8, seed=5)
    lens = jnp.zeros((8,), jnp.float32)
    out = mha(q, k, v, lens, block_q=32, block_k=32, interpret=True)
    assert np.all(np.asarray(out) == 0.0)
    grads = jax.grad(lambda q, k, v: mha(q, k, v, lens, block_q=32,
                                         block_k=32, interpret=True).sum(),
                     (0, 1, 2))(q, k, v)
    for g, name in zip(grads, ("dq", "dk", "dv")):
        arr = np.asarray(g)
        assert np.isfinite(arr).all(), f"{name} has non-finite entries"
        np.testing.assert_array_equal(arr, 0.0, err_msg=name)


def test_mha_no_lengths_is_dense_attention():
    q, k, v, _ = _mha_case(40, 16, seed=9)
    out = mha(q, k, v, block_q=32, block_k=32, interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_mha_lse_rows_match_masked_logsumexp():
    """The forward's (BH, 1, Sq) log-sum-exp rows — one per query, across
    two query blocks — equal the masked reference logsumexp."""
    from repro.kernels.flash_attention.ops import _mha_fwd_impl
    S, dh = 129, 16
    q, k, v, lens = _mha_case(S, dh, seed=21)
    _, lse = _mha_fwd_impl(q, k, v, lens, 128, 128, True)
    assert lse.shape == (q.shape[0], 1, S)
    s = jnp.einsum("bqd,bkd->bqk", q, k) * dh ** -0.5
    keep = jnp.arange(S)[None, None, :] < lens[:, None, None]
    ref = jax.nn.logsumexp(jnp.where(keep, s, -jnp.inf), axis=-1)
    rows = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(lse)[rows, 0],
                               np.asarray(ref)[rows], rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ window pack
# J spans several 256-job chunks, so the waiting count carries across
# grid steps; N is no multiple of the 8-row env block.
@pytest.mark.parametrize("n,j,window,density", [
    (5, 700, 10, 0.4), (3, 1000, 128, 0.2), (9, 600, 10, 0.01)])
def test_window_pack_multi_chunk_matches_reference(n, j, window, density):
    from repro.kernels.window_pack.ops import pack_window
    rng = np.random.default_rng(j)
    waiting = (rng.uniform(size=(n, j)) < density).astype(np.float32)
    feats = rng.normal(size=(n, j, 4)).astype(np.float32) * 1e5
    ref = pack_window(waiting, feats, window=window, use_pallas=False)
    ker = pack_window(waiting, feats, window=window, use_pallas=True,
                      interpret=True)
    for got, want in zip(ker, ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -------------------------------------------------------------------- ssd
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 8, 16), (2, 100, 3, 16, 8, 32), (1, 256, 4, 32, 16, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_matches_sequential_ref(B, S, H, P, N, chunk, dtype):
    key = jax.random.PRNGKey(S)
    x = jax.random.normal(key, (B, S, H, P), dtype)
    dt = jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(key, 1), (B, S, H)))
    dA = -dt * jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.3)
    Bm = (jax.random.normal(jax.random.fold_in(key, 3), (B, S, H, N)) * 0.3
          ).astype(dtype)
    Cm = (jax.random.normal(jax.random.fold_in(key, 4), (B, S, H, N)) * 0.3
          ).astype(dtype)
    y = ssd(x, dt, dA, Bm, Cm, chunk=chunk)

    def flat(t, d):
        return t.transpose(0, 2, 1, 3).reshape(B * H, S, d)

    yr = ssd_ref(flat(x, P), dt.transpose(0, 2, 1).reshape(B * H, S, 1),
                 dA.transpose(0, 2, 1).reshape(B * H, S, 1),
                 flat(Bm, N), flat(Cm, N))
    yr = yr.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               **(dict(rtol=5e-2, atol=5e-2)
                                  if dtype == jnp.bfloat16 else
                                  dict(rtol=1e-3, atol=1e-3)))


def test_model_chunked_ssd_matches_sequential_ref():
    """The vectorized chunked SSD inside the model (associative scan) must
    also match the exact recurrence."""
    from repro.models.mamba2 import _ssd_chunked
    key = jax.random.PRNGKey(9)
    B, S, H, P, N, chunk = 2, 128, 4, 16, 8, 32
    x = jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (B, S, H)))
    dA = -dt * 0.4
    Bm = jax.random.normal(jax.random.fold_in(key, 2), (B, S, 1, N)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, 1, N)) * 0.3
    y = _ssd_chunked(x, dt, dA, Bm, Cm, chunk)

    def flat(t, d):
        return jnp.repeat(t, H, axis=2).transpose(0, 2, 1, 3).reshape(
            B * H, S, d) if t.shape[2] == 1 else \
            t.transpose(0, 2, 1, 3).reshape(B * H, S, d)

    yr = ssd_ref(x.transpose(0, 2, 1, 3).reshape(B * H, S, P),
                 dt.transpose(0, 2, 1).reshape(B * H, S, 1),
                 dA.transpose(0, 2, 1).reshape(B * H, S, 1),
                 flat(Bm, N), flat(Cm, N))
    yr = yr.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-3,
                               atol=1e-3)
