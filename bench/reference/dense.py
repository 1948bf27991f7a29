"""Plain reference of a dense decoder-only language model and of one
DeMo training step, written from the published descriptions and
importing nothing of the program under test.

Model: pre-norm decoder blocks (Llama / Qwen2 style). RMSNorm with a
gain; attention with rotary position embeddings (rotate-half form,
frequencies theta^(-2i/head_dim)), grouped key/value heads, optional
q/k/v biases, causal softmax scaled by 1/sqrt(head_dim); SwiGLU MLP
(down(silu(gate x) * up x)); a final RMSNorm; logits against the output
head, or against the input embedding where the two are tied. The loss
is the mean next-token cross-entropy over ``logit_rows`` classes.

DeMo step (arXiv:2411.19870), per parameter tensor: error feedback
e <- beta*e + g; the tensor is viewed in 2-D (all leading axes
collapsed; a 1-D tensor wrapped to rows of ``chunk``), zero-padded to
whole ``chunk`` x ``chunk`` tiles, and each tile takes an orthonormal
2-D DCT-II; the ``topk`` largest-magnitude coefficients of each tile
are sent; e <- e - idct(sent). One peer's update is the sign of the
decoded sent coefficients, applied with decoupled weight decay:
theta <- theta*(1 - lr*wd) - lr*sign(idct(sent)).

``mode`` sets the arithmetic: "float32" computes every matrix product
at full float32 precision (the reference); "fp8" rounds both operands
of every matrix product, and the incoming gradient of each in the
backward pass, to float8 e4m3 with one scale per tensor, and
accumulates in float32 (the low-precision control). ``flip_update``
applies the sign update the wrong way (gradient ascent): a planted
fault, read in the program's place.

Layer leaves carry a leading layer axis. With ``stacked`` the DeMo
codec sees each such leaf whole, as a program that scans over stacked
layers holds it; otherwise it compresses every layer's slice on its
own.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _q8(x):
    s = jnp.max(jnp.abs(x)) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    return _einsum(spec, _q8(a), _q8(b))


def _einsum_fp8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _einsum_fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), qa, qb)
    return vjp(_q8(g))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def matmul(mode: str):
    if mode == "float32":
        return _einsum
    if mode == "fp8":
        return _einsum_fp8
    raise ValueError(f"unknown mode {mode!r}")


# ------------------------------------------------------------- model


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (b, s, heads, hd): rotate-half rotary embedding at 0..s-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _linear(mm, p, x):
    y = mm("bsd,df->bsf", x, p["w"])
    return y + p["b"] if "b" in p else y


def _block(c, mm, x, p):
    b, s, _ = x.shape
    H, Hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    h = _rmsnorm(x, p["norm1"]["g"], c["rms_norm_eps"])
    a = p["attn"]
    q = _linear(mm, a["wq"], h).reshape(b, s, H, hd)
    k = _linear(mm, a["wk"], h).reshape(b, s, Hkv, hd)
    v = _linear(mm, a["wv"], h).reshape(b, s, Hkv, hd)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mm("bhqk,bkhd->bqhd", probs, v).reshape(b, s, H * hd)
    x = x + _linear(mm, a["wo"], o)
    h = _rmsnorm(x, p["norm2"]["g"], c["rms_norm_eps"])
    m = p["mlp"]
    ff = jax.nn.silu(_linear(mm, m["gate"], h)) * _linear(mm, m["up"], h)
    return x + _linear(mm, m["down"], ff)


def loss(c, mm, params, tokens, labels):
    """Mean next-token cross-entropy of a block of rows."""
    x = params["embed"]["w"][tokens].astype(jnp.float32)
    body = jax.checkpoint(lambda x, p: (_block(c, mm, x, p), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"]["g"], c["rms_norm_eps"])
    if c["tie_word_embeddings"]:
        logits = mm("bsd,vd->bsv", x, params["embed"]["w"])
    else:
        logits = mm("bsd,dv->bsv", x, params["lm_head"]["w"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


# -------------------------------------------------------------- DeMo


def _dct_matrix(s: int) -> np.ndarray:
    k = np.arange(s)[:, None]
    n = np.arange(s)[None, :]
    m = np.cos(np.pi * (2 * n + 1) * k / (2 * s)) * math.sqrt(2.0 / s)
    m[0] /= math.sqrt(2.0)
    return m.astype(np.float32)


def _view2d(shape, s):
    if len(shape) == 1:
        cols = min(s, shape[0])
        return -(-shape[0] // cols), cols
    return int(np.prod(shape[:-1])), shape[-1]


def _tiles(x, s):
    """Tensor -> (tile rows, s, tile cols, s), zero-padded."""
    r, c = _view2d(x.shape, s)
    flat = jnp.pad(x.reshape(-1), (0, r * c - x.size)).reshape(r, c)
    flat = jnp.pad(flat, ((0, -r % s), (0, -c % s)))
    return flat.reshape(flat.shape[0] // s, s, flat.shape[1] // s, s)


def _untile(t, shape, s):
    r, c = _view2d(shape, s)
    flat = t.reshape(t.shape[0] * s, t.shape[2] * s)[:r, :c]
    return flat.reshape(-1)[:int(np.prod(shape))].reshape(shape)


def demo_leaf(e, g, *, beta, chunk, topk):
    """(new error feedback, sign of the sent part) for one tensor."""
    m = jnp.asarray(_dct_matrix(chunk))
    e = beta * e + g
    t = _tiles(e, chunk)
    coef = jnp.einsum("ij,rjcl,kl->rcik", m, t, m, precision=HIGHEST)
    R, C = coef.shape[:2]
    flat = coef.reshape(R * C, chunk * chunk)
    _, idx = jax.lax.top_k(jnp.abs(flat), topk)
    rows = jnp.arange(R * C)[:, None]
    kept = jnp.zeros_like(flat).at[rows, idx].set(flat[rows, idx])
    kept = kept.reshape(R, C, chunk, chunk)
    sent = jnp.einsum("ji,rcjl,lk->rick", m, kept, m, precision=HIGHEST)
    sent = _untile(sent, e.shape, chunk)
    return e - sent, jnp.sign(sent)


def lr_at(step: int, h: dict) -> float:
    """Linear warm-up to ``learning_rate``, then a cosine down to
    ``lr_min_frac`` of it at ``total_steps``."""
    base, warm, total = h["learning_rate"], h["warmup_steps"], h["total_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    frac = h["lr_min_frac"]
    return base * (frac + (1 - frac) * 0.5 * (1 + math.cos(math.pi * prog)))


def _norms(tree, stacked: bool):
    """Per-tensor L2 norms as the codec sees the tensors: layer leaves
    whole when ``stacked``, else one norm per layer."""
    def one(path, x):
        if path[0].key == "layers" and not stacked:
            return jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)),
                                    axis=1))
        return jnp.sqrt(jnp.sum(jnp.square(x)))
    return jax.tree_util.tree_map_with_path(one, tree)


def named(norm_tree) -> dict:
    """``{leaf name: norm}`` from a tree of norms (``layers.3.attn.wq.w``
    for one layer's slice, ``layers.attn.wq.w`` for a stacked leaf)."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(norm_tree)[0]:
        name = ".".join(str(k.key) for k in path)
        v = np.asarray(v)
        if v.ndim:
            for i, x in enumerate(v):
                out[name.replace("layers.", f"layers.{i}.", 1)] = float(x)
        else:
            out[name] = float(v)
    return out


def _host(tree, stacked: bool) -> dict:
    """``{leaf name: host array}``, named as ``named`` names the norms:
    a layer leaf split into its layers unless ``stacked``."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = ".".join(str(k.key) for k in path)
        x = np.asarray(x)
        if path[0].key == "layers" and not stacked:
            for i, xi in enumerate(x):
                out[name.replace("layers.", f"layers.{i}.", 1)] = xi
        else:
            out[name] = x
    return out


@functools.lru_cache(maxsize=None)
def _step_fns(c_json: str, h_json: str, mode: str, stacked: bool,
              flip_update: bool):
    """Two programs per step, so that the gradient's and the codec's
    temporaries never share the chip: ``grad(params, tokens, labels)``
    with tokens/labels (blocks, rows, seq), the loss and gradient the
    mean over the blocks; ``update(params, ef, grads, lr)``."""
    c, h = json.loads(c_json), json.loads(h_json)
    mm = matmul(mode)
    leaf = functools.partial(demo_leaf, beta=h["demo_beta"],
                             chunk=h["demo_chunk"], topk=h["demo_topk"])
    layer_leaf = leaf if stacked else jax.vmap(leaf)
    sign = 1.0 if flip_update else -1.0
    pair = lambda o: isinstance(o, tuple)  # noqa: E731

    def grad(params, tokens, labels):
        def acc(carry, blk):
            l_sum, g_sum = carry
            l, g = jax.value_and_grad(
                lambda p: loss(c, mm, p, *blk))(params)
            return (l_sum + l, jax.tree.map(jnp.add, g_sum, g)), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (l_sum, g_sum), _ = jax.lax.scan(acc, (jnp.float32(0), zeros),
                                         (tokens, labels))
        n = tokens.shape[0]
        return l_sum / n, jax.tree.map(lambda g: g / n, g_sum)

    def update(params, ef, grads, lr):
        new_p, new_e = {}, {}
        for key in params:
            fn = layer_leaf if key == "layers" else leaf
            out = jax.tree.map(fn, ef[key], grads[key])
            new_e[key] = jax.tree.map(lambda o: o[0], out, is_leaf=pair)
            new_p[key] = jax.tree.map(
                lambda p, o: p * (1 - lr * h["weight_decay"])
                + sign * lr * o[1], params[key], out, is_leaf=pair)
        return new_p, new_e

    return jax.jit(grad), jax.jit(update, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _norms_fn(stacked: bool):
    return jax.jit(lambda t: _norms(t, stacked))


@functools.lru_cache(maxsize=None)
def _change_fn(stacked: bool):
    return jax.jit(lambda a, b: _norms(jax.tree.map(jnp.subtract, a, b),
                                       stacked))


def train_readings(c: dict, h: dict, params, batches, *, mode: str,
                   stacked: bool, initial, flip_update: bool = False):
    """Run ``len(batches)`` DeMo steps from ``params`` (consumed) and
    return what the check compares: the loss of every step, per-tensor
    norms of the first step's gradient and of the error feedback after
    it, and of the change of the parameters over all the steps
    (``initial()`` remakes the starting parameters for that), and the
    error feedback and the change themselves as host arrays."""
    blocks = h["microbatch"]
    grad, update = _step_fns(json.dumps(c, sort_keys=True),
                             json.dumps(h, sort_keys=True), mode, stacked,
                             flip_update)
    norms = _norms_fn(stacked)
    ef = jax.tree.map(jnp.zeros_like, params)
    losses, out = [], {}
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            tok = b["tokens"].reshape((blocks, -1) + b["tokens"].shape[1:])
            lab = b["labels"].reshape(tok.shape)
            l, grads = grad(params, tok, lab)
            if i == 0:
                out["grad1"] = named(norms(grads))
            params, ef = update(params, ef, grads,
                                jnp.float32(lr_at(i, h)))
            del grads
            losses.append(float(l))
            if i == 0:
                out["ef1"] = named(norms(ef))
                out["ef1_vec"] = _host(ef, stacked)
        del ef
        start = initial()
        out["change"] = named(_change_fn(stacked)(params, start))
        out["change_vec"] = _host(jax.tree.map(
            lambda a, b: np.asarray(a - b), params, start), stacked)
    out["losses"] = losses
    return out
