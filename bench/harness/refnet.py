"""The policy network, written out plainly, and its weights from a seed.

Direct Future Prediction as MRSch uses it (arXiv:2403.16298, Sec. II-B,
III, IV-C): a state module (an MLP ``state_dim -> 4000 -> 1000 -> 512``,
or a queue-as-tokens attention encoder), measurement and goal modules of
three 128-wide layers, a joint representation feeding an expectation
stream and an action stream (dueling: the action stream is centred over
actions), and action values ``u(a) = sum_t w_t sum_m g_m p[a, t, m]``.
Every layer uses a leaky rectifier of slope 0.2, except the last layer
of each stream.

``make_params`` draws the weights on the device in one jitted call from
the seed, as a pytree in the layout the program takes
(``{"layers": [{"w", "b"}, ...]}`` per module).  ``scores`` is the
reference: float32 at ``Precision.HIGHEST`` (or, for a control, in a
lower precision), applied in blocks of rows.

Nothing here imports the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SLOPE = 0.2
LN_EPS = 1e-5


def _leaky(x):
    return jnp.where(x >= 0, x, SLOPE * x)


# ----------------------------------------------------------------- shapes
def module_sizes(cfg: dict) -> dict:
    """Dense layer sizes of each MLP module of the configuration."""
    R = len(cfg["resources"])
    T = len(cfg["offsets"])
    W = cfg["window"]
    h, sh, so = cfg["module_hidden"], cfg["stream_hidden"], cfg["state_out"]
    joint = so + 2 * h
    out = {"measurement": [R, h, h, h], "goal": [R, h, h, h],
           "expectation": [joint, sh, T * R],
           "action": [joint, sh, W * T * R]}
    if cfg["state_module"] == "mlp":
        out["state"] = [state_dim(cfg), *cfg["state_hidden"], so]
    return out


def state_dim(cfg: dict) -> int:
    R, jd = len(cfg["resources"]), len(cfg["resources"]) + 2
    if cfg["state_module"] == "attention":
        return cfg["queue_cap"] * jd + 1 + 2 * R
    return cfg["window"] * jd + 2 * (cfg["nodes"] + cfg["bb_units"])


def dense_layers(cfg: dict, batch_rows: int) -> list:
    """(M, K, N) of every dense layer one forward over ``batch_rows``
    decision rows runs (attention layers count each token as a row)."""
    out = []
    for sizes in module_sizes(cfg).values():
        out += [(batch_rows, k, n) for k, n in zip(sizes[:-1], sizes[1:])]
    if cfg["state_module"] == "attention":
        R, W, d = len(cfg["resources"]), cfg["window"], cfg["attn_dim"]
        S = cfg["queue_cap"] + 1
        out += [(batch_rows * cfg["queue_cap"], R + 2, d), (batch_rows, 2 * R, d)]
        for _ in range(cfg["attn_layers"]):
            out += [(batch_rows * S, d, d)] * 4
            out += [(batch_rows * S, d, cfg["attn_mlp_mult"] * d),
                    (batch_rows * S, cfg["attn_mlp_mult"] * d, d)]
        out += [(batch_rows, d * (2 + W), cfg["state_out"])]
    return out


# ---------------------------------------------------------------- weights
def _dense_init(key, k, n, bias_std):
    kw, kb = jax.random.split(key)
    return {"w": jax.random.normal(kw, (k, n), jnp.float32) * math.sqrt(2.0 / k),
            "b": jax.random.normal(kb, (n,), jnp.float32) * bias_std}


def _mlp_init(key, sizes, bias_std):
    keys = jax.random.split(key, len(sizes) - 1)
    return {"layers": [_dense_init(kk, a, b, bias_std)
                       for kk, a, b in zip(keys, sizes[:-1], sizes[1:])]}


def _ln_init(key, d, std):
    ks, kb = jax.random.split(key)
    return {"scale": 1.0 + std * jax.random.normal(ks, (d,), jnp.float32),
            "bias": std * jax.random.normal(kb, (d,), jnp.float32)}


def _attention_params(key, cfg, bias_std):
    R, W, d = len(cfg["resources"]), cfg["window"], cfg["attn_dim"]
    ks = jax.random.split(key, 4 + cfg["attn_layers"])
    blocks = []
    for i in range(cfg["attn_layers"]):
        bk = jax.random.split(ks[4 + i], 7)
        blocks.append({
            "ln1": _ln_init(bk[5], d, bias_std),
            "wq": _dense_init(bk[0], d, d, bias_std),
            "wk": _dense_init(bk[1], d, d, bias_std),
            "wv": _dense_init(bk[2], d, d, bias_std),
            "wo": _dense_init(bk[3], d, d, bias_std),
            "ln2": _ln_init(bk[6], d, bias_std),
            "mlp": _mlp_init(bk[4], [d, cfg["attn_mlp_mult"] * d, d], bias_std),
        })
    return {"tok": _dense_init(ks[0], R + 2, d, bias_std),
            "ctx": _dense_init(ks[1], 2 * R, d, bias_std),
            "blocks": blocks,
            "ln_f": _ln_init(ks[3], d, bias_std),
            "out": _dense_init(ks[2], d * (2 + W), cfg["state_out"], bias_std)}


@functools.partial(jax.jit, static_argnums=(0,))
def _build_params(cfg_key, key):
    cfg = dict(cfg_key)
    bias_std = float(cfg["bias_std"])
    sizes = module_sizes(cfg)
    names = ("state", "measurement", "goal", "expectation", "action")
    keys = dict(zip(names, jax.random.split(key, len(names))))
    out = {n: _mlp_init(keys[n], sizes[n], bias_std)
           for n in names if n in sizes}
    if cfg["state_module"] == "attention":
        out["state"] = _attention_params(keys["state"], cfg, bias_std)
    return out


def make_params(cfg: dict, seed: int, index: int = 0):
    """Every weight of policy ``index`` of ``seed``, on the device, in one
    jitted call."""
    word = int(np.random.SeedSequence([int(seed), 7, int(index)]).generate_state(1)[0])
    key = _freeze(cfg) + (("bias_std", float(cfg["init"]["bias_std"])),)
    return _build_params(key, jax.random.PRNGKey(word))


# ---------------------------------------------------------------- forward
# Precision modes: "f32" is the reference (float32 at Precision.HIGHEST);
# "bf16" computes at the configurations' stated precision (bfloat16
# operands and results); "int8" (W8A8) is the control, one step below.
MODES = ("f32", "bf16", "int8")


def _int8(x, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``,
    returned dequantized (what an int8 kernel's operands hold)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _dtype(mode):
    return jnp.bfloat16 if mode == "bf16" else jnp.float32


def _dot(x, w, mode):
    if mode == "int8":                # W8A8: per-row x, per-column w
        return jnp.dot(_int8(x.astype(jnp.float32), -1),
                       _int8(w.astype(jnp.float32), 0),
                       precision=jax.lax.Precision.HIGHEST)
    prec = (jax.lax.Precision.HIGHEST if mode == "f32"
            else jax.lax.Precision.DEFAULT)
    dt = _dtype(mode)
    return jnp.dot(x.astype(dt), w.astype(dt), precision=prec,
                   preferred_element_type=dt)


def _einsum(spec, *operands, mode):
    prec = (jax.lax.Precision.HIGHEST if mode == "f32"
            else jax.lax.Precision.DEFAULT)
    return jnp.einsum(spec, *operands, precision=prec)


def _dense(layer, x, mode):
    return _dot(x, layer["w"], mode) + layer["b"].astype(_dtype(mode))


def _mlp_fwd(p, x, final_act, mode):
    n = len(p["layers"])
    for i, layer in enumerate(p["layers"]):
        x = _dense(layer, x, mode)
        if i < n - 1 or final_act:
            x = _leaky(x)
    return x


def _layer_norm(p, x, mode):
    dt = _dtype(mode)
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"].astype(dt)
            + p["bias"].astype(dt))


def _attention_state(p, cfg, state, mode):
    """Queue-as-tokens encoder: context token plus Q job tokens, pre-norm
    blocks of masked softmax attention (keys beyond 1 + queue length
    masked) and a two-layer MLP; pooled as [context token | masked mean
    of job tokens | first W job tokens] -> dense -> leaky rectifier."""
    R, W, d, H = (len(cfg["resources"]), cfg["window"], cfg["attn_dim"],
                  cfg["attn_heads"])
    Q, jd = cfg["queue_cap"], R + 2
    B = state.shape[0]
    dt = _dtype(mode)
    tokens = state[:, :Q * jd].reshape(B, Q, jd)
    qlen = state[:, Q * jd].astype(jnp.float32)
    ctx = state[:, Q * jd + 1:Q * jd + 1 + 2 * R]
    x = jnp.concatenate([_dense(p["ctx"], ctx, mode)[:, None],
                         _dense(p["tok"], tokens, mode)], axis=1)
    S, hd = Q + 1, d // H
    kmask = jnp.arange(S)[None, :] < (qlen + 1.0)[:, None]          # (B, S)
    for blk in p["blocks"]:
        h = _layer_norm(blk["ln1"], x, mode)
        q = _dense(blk["wq"], h, mode).reshape(B, S, H, hd)
        k = _dense(blk["wk"], h, mode).reshape(B, S, H, hd)
        v = _dense(blk["wv"], h, mode).reshape(B, S, H, hd)
        s = _einsum("bqhd,bkhd->bhqk", q, k, mode=mode) * hd ** -0.5
        s = jnp.where(kmask[:, None, None, :], s.astype(jnp.float32), -1e30)
        a = jax.nn.softmax(s, axis=-1)
        a = jnp.where(kmask[:, None, None, :], a, 0.0).astype(dt)
        o = _einsum("bhqk,bkhd->bqhd", a, v, mode=mode)
        x = x + _dense(blk["wo"], o.reshape(B, S, d), mode)
        h2 = _layer_norm(blk["ln2"], x, mode)
        m = _leaky(_dense(blk["mlp"]["layers"][0], h2, mode))
        x = x + _dense(blk["mlp"]["layers"][1], m, mode)
    h = _layer_norm(p["ln_f"], x, mode)
    jobs = h[:, 1:]
    valid = (jnp.arange(Q)[None, :] < qlen[:, None]).astype(dt)
    mean = ((jobs * valid[..., None]).sum(axis=1)
            / jnp.maximum(valid.sum(axis=1, keepdims=True), 1.0))
    win = jobs[:, :W] * valid[:, :W, None]
    feat = jnp.concatenate([h[:, 0], mean, win.reshape(B, W * d)], axis=-1)
    return _leaky(_dense(p["out"], feat, mode))


@functools.partial(jax.jit, static_argnums=(1, 3))
def _scores(params, cfg_key, rows, mode):
    cfg = dict(cfg_key)
    R, W, T = len(cfg["resources"]), cfg["window"], len(cfg["offsets"])
    sd = state_dim(cfg)
    rows = rows.astype(_dtype(mode))
    state, meas, goal = rows[:, :sd], rows[:, sd:sd + R], rows[:, sd + R:sd + 2 * R]
    if cfg["state_module"] == "attention":
        s = _attention_state(params["state"], cfg, state, mode)
    else:
        s = _mlp_fwd(params["state"], state, True, mode)
    m = _mlp_fwd(params["measurement"], meas, True, mode)
    g = _mlp_fwd(params["goal"], goal, True, mode)
    j = jnp.concatenate([s, m, g], axis=-1)
    e = _mlp_fwd(params["expectation"], j, False, mode)
    a = _mlp_fwd(params["action"], j, False, mode)
    a = a.reshape(-1, W, T * R)
    a = a - a.mean(axis=1, keepdims=True)
    p = (e[:, None, :] + a).reshape(-1, W, T, R)
    w = jnp.asarray(cfg["temporal_weights"], _dtype(mode))
    return _einsum("batm,t,bm->ba", p, w, goal, mode=mode)


_NET_KEYS = ("resources", "window", "offsets", "temporal_weights",
             "state_module", "state_hidden", "state_out", "module_hidden",
             "stream_hidden", "queue_cap", "attn_dim", "attn_heads",
             "attn_layers", "attn_mlp_mult", "nodes", "bb_units")


def _freeze(cfg: dict):
    def f(v):
        return tuple(f(x) for x in v) if isinstance(v, list) else v
    return tuple((k, f(cfg[k])) for k in _NET_KEYS if k in cfg)


def scores(params, cfg: dict, rows: np.ndarray, *, block: int = 512,
           mode: str = "f32") -> np.ndarray:
    """Action values (rows, W) of packed decision rows, in blocks."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    key = _freeze(cfg)
    out = []
    for i in range(0, len(rows), block):
        chunk = rows[i:i + block]
        n = len(chunk)
        if n < block:                  # one shape per block size
            chunk = np.concatenate(
                [chunk, np.zeros((block - n, rows.shape[1]), rows.dtype)])
        u = _scores(params, key, jnp.asarray(chunk), mode)
        out.append(np.asarray(u, np.float32)[:n])
    return np.concatenate(out) if out else np.zeros((0, cfg["window"]), np.float32)
