"""Plain float32 reference of the repository's dense GQA decoder LM.

It follows the layer equations of the block the benchmark runs, written out in
``jax.numpy`` with no kernels, caches, scans or batching, every matmul at
``jax.default_matmul_precision("highest")``:

    x  = rmsnorm(h) * ln
    q, k, v = x Wq, x Wk, x Wv            (GQA: n_kv_heads K/V heads)
    q, k = rope(q), rope(k)               (rotate-half over the whole head)
    h += softmax(q k^T / sqrt(hd) + causal) v Wo
    x  = rmsnorm(h) * ln2
    h += (silu(x Wg) * (x Wi)) Wo_mlp
    logits = (rmsnorm(h) * final_norm) W_head
    loss = mean(lse - logit[target]) + 1e-4 * mean(lse^2)

and AdamW with global-norm clipping, bias correction, decoupled weight decay
on an f32 master copy, and the linear-warmup cosine learning rate.  Training
runs layer by layer over one sequence at a time, with the layers split over
the cell's chips where one chip cannot hold their f32 state.  Where
this block departs from the published GLM-4-9B layer (no QKV bias, full-head
rotate-half RoPE) the configuration file lists it under ``departures``.

Weights come from ``--seed`` by one of two rules, both written out here so the
reference never takes an array from the program:

* ``stacked``: one key per parameter leaf (``split(PRNGKey(seed), n_leaves)``
  in the leaf order below), each leaf drawn whole, layers stacked on axis 0.
  It is the training program's own initialization, and it is drawn as the
  program draws it, one operation at a time outside ``jit``: inside one
  jitted graph XLA may fold the scale into the normal's arithmetic, which
  moves a few elements by one bfloat16 step;
* ``per_layer``: the same leaf keys, layer ``i`` of a stacked leaf drawn from
  ``fold_in(key, i)`` -- so one layer can be rebuilt alone.  The serving
  cells' weights are made by this rule.

Normal leaves are ``normal * scale`` rounded to bfloat16 (the served type),
scale 0.02 for the embedding and ``fan_in ** -0.5`` otherwise; norm scales
are ones.  ``precision="fp8"`` quantizes both operands of every matmul to
float8_e4m3fn with a per-tensor (weights) or per-row (activations) scale:
the control that the comparison must reject.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Arch",
    "LEAVES",
    "leaf_shape",
    "make_weights",
    "layer_weights",
    "stacked_leaf",
    "stacked_weights",
    "top_weights",
    "loss_fn",
    "train_reference",
    "adamw_update",
    "clip_scale",
    "served_token_gaps",
    "lr_at",
    "diff_norm",
]

Z_COEF = 1e-4


@dataclass(frozen=True)
class Arch:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    n_layers: int
    norm_eps: float
    rope_theta: float


#: (name, stacked, init) in the order the leaves take their keys
LEAVES: tuple[tuple[str, bool, str], ...] = (
    ("embed.tokens", False, "embed"),
    ("final_norm", False, "ones"),
    ("lm_head", False, "normal"),
    ("ln", True, "ones"),
    ("ln2", True, "ones"),
    ("mlp.wg", True, "normal"),
    ("mlp.wi", True, "normal"),
    ("mlp.wo", True, "normal"),
    ("wk", True, "normal"),
    ("wo", True, "normal"),
    ("wq", True, "normal"),
    ("wv", True, "normal"),
)
_STACKED = tuple(n for n, s, _ in LEAVES if s)


def leaf_shape(a: Arch, name: str) -> tuple[int, ...]:
    """Shape of one layer's slice (stacked leaves) or of the whole leaf."""
    q, kv = a.n_heads * a.head_dim, a.n_kv_heads * a.head_dim
    return {
        "embed.tokens": (a.vocab, a.d_model),
        "final_norm": (a.d_model,),
        "lm_head": (a.d_model, a.vocab),
        "ln": (a.d_model,),
        "ln2": (a.d_model,),
        "mlp.wg": (a.d_model, a.d_ff),
        "mlp.wi": (a.d_model, a.d_ff),
        "mlp.wo": (a.d_ff, a.d_model),
        "wk": (a.d_model, kv),
        "wo": (q, a.d_model),
        "wq": (a.d_model, q),
        "wv": (a.d_model, kv),
    }[name]


def _draw(key, shape, init: str, dtype):
    if init == "ones":
        return jnp.ones(shape, dtype)
    scale = 0.02 if init == "embed" else shape[-2] ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _leaf_keys(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed), len(LEAVES))


@functools.partial(jax.jit, static_argnums=(0, 2))
def make_weights(a: Arch, seed, dtype=jnp.bfloat16) -> dict:
    """Every leaf of the ``per_layer`` rule, stacked leaves with the layer
    axis first, in ``dtype``: one jitted call on the device."""
    keys = _leaf_keys(seed)
    out = {}
    for (name, stacked, init), key in zip(LEAVES, keys):
        shape = leaf_shape(a, name)
        if not stacked:
            out[name] = _draw(key, shape, init, jnp.bfloat16).astype(dtype)
        else:
            out[name] = jnp.stack([
                _draw(jax.random.fold_in(key, i), shape, init, jnp.bfloat16).astype(dtype)
                for i in range(a.n_layers)
            ])
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def layer_weights(a: Arch, seed, layer) -> dict:
    """Layer ``layer`` of the ``per_layer`` rule, bf16 values held in f32."""
    keys = _leaf_keys(seed)
    out = {}
    for (name, stacked, init), key in zip(LEAVES, keys):
        if stacked:
            w = _draw(jax.random.fold_in(key, layer), leaf_shape(a, name), init, jnp.bfloat16)
            out[name] = w.astype(jnp.float32)
    return out


def stacked_leaf(a: Arch, seed: int, name: str):
    """One whole leaf of the ``stacked`` rule, bf16 values held in f32, drawn
    one operation at a time as the training program draws it (not jitted)."""
    i = [n for n, _, _ in LEAVES].index(name)
    _, stacked, init = LEAVES[i]
    shape = ((a.n_layers,) if stacked else ()) + leaf_shape(a, name)
    return _draw(_leaf_keys(seed)[i], shape, init, jnp.bfloat16).astype(jnp.float32)


def stacked_weights(a: Arch, seed: int) -> dict:
    """Every leaf of the ``stacked`` rule (see :func:`stacked_leaf`)."""
    return {name: stacked_leaf(a, seed, name) for name, _, _ in LEAVES}


@functools.partial(jax.jit, static_argnums=(0,))
def top_weights(a: Arch, seed) -> dict:
    """Embedding, final norm and head (identical under both rules), as f32."""
    keys = _leaf_keys(seed)
    return {
        name: _draw(key, leaf_shape(a, name), init, jnp.bfloat16).astype(jnp.float32)
        for (name, stacked, init), key in zip(LEAVES, keys)
        if not stacked
    }


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    """Round to float8_e4m3fn with an amax scale over ``axis`` (None: tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, precision: str):
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, None)
    return x @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, H, hd); rotate-half over the whole head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(a: Arch, q, k, v):
    """Causal GQA over one sequence. q: (S, H, hd); k, v: (S, KV, hd)."""
    s = q.shape[0]
    g = a.n_heads // a.n_kv_heads
    mask = jnp.tril(jnp.ones((s, s), bool))
    outs = []
    for j in range(a.n_kv_heads):
        qj = q[:, j * g:(j + 1) * g]                       # (S, G, hd)
        sc = jnp.einsum("sgd,td->gst", qj, k[:, j]) / math.sqrt(a.head_dim)
        sc = jnp.where(mask[None], sc, -jnp.inf)
        outs.append(jnp.einsum("gst,td->sgd", jax.nn.softmax(sc, axis=-1), v[:, j]))
    return jnp.concatenate(outs, axis=1)                  # (S, H, hd)


def layer_apply(a: Arch, w: dict, h, precision: str = "f32"):
    """One decoder layer over one sequence h: (S, D)."""
    s = h.shape[0]
    pos = jnp.arange(s)
    x = _rms(h, w["ln"], a.norm_eps)
    q = _mm(x, w["wq"], precision).reshape(s, a.n_heads, a.head_dim)
    k = _mm(x, w["wk"], precision).reshape(s, a.n_kv_heads, a.head_dim)
    v = _mm(x, w["wv"], precision).reshape(s, a.n_kv_heads, a.head_dim)
    q, k = _rope(q, pos, a.rope_theta), _rope(k, pos, a.rope_theta)
    att = _attention(a, q, k, v).reshape(s, -1)
    h = h + _mm(att, w["wo"], precision)
    x = _rms(h, w["ln2"], a.norm_eps)
    hidden = jax.nn.silu(_mm(x, w["mlp.wg"], precision)) * _mm(x, w["mlp.wi"], precision)
    return h + _mm(hidden, w["mlp.wo"], precision)


def _logits(a: Arch, top: dict, h, precision: str):
    return _mm(_rms(h, top["final_norm"], a.norm_eps), top["lm_head"], precision)


def loss_fn(a: Arch, w: dict, tokens, targets, precision: str = "f32"):
    """Mean over the valid targets (``>= 0``) of CE plus the z-loss.
    tokens, targets: (B, S)."""
    layer = jax.checkpoint(functools.partial(layer_apply, a, precision=precision))

    def one(tok, tgt):
        h = w["embed.tokens"][tok]
        for i in range(a.n_layers):
            h = layer({n: w[n][i] for n in _STACKED}, h)
        logits = _logits(a, w, h, precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(tgt, 0)[:, None], axis=-1)[:, 0]
        valid = (tgt >= 0).astype(jnp.float32)
        return jnp.sum((lse - gold) * valid), jnp.sum(lse * lse * valid), jnp.sum(valid)

    ce, z, n = 0.0, 0.0, 0.0
    for b in range(tokens.shape[0]):
        c, zz, nn = one(tokens[b], targets[b])
        ce, z, n = ce + c, z + zz, n + nn
    n = jnp.maximum(n, 1.0)
    return ce / n + Z_COEF * z / n


# ---------------------------------------------------------------------------
# Training: three AdamW steps
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AdamW:
    peak_lr: float
    warmup_steps: int
    total_steps: int
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    final_frac: float = 0.1


def lr_at(o: AdamW, step: int) -> float:
    if step < o.warmup_steps:
        return o.peak_lr * step / max(o.warmup_steps, 1)
    prog = min(max((step - o.warmup_steps) / max(o.total_steps - o.warmup_steps, 1), 0.0), 1.0)
    return o.peak_lr * (o.final_frac + (1 - o.final_frac) * 0.5 * (1 + math.cos(math.pi * prog)))


@jax.jit
def diff_norm(x, y):
    """The norm of ``x - y``: a leaf's change from its initial value."""
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y)))


def _rounded(w: dict) -> dict:
    # the program computes in bf16 from its f32 master: the loss sees the
    # master rounded to bf16, and the gradient flows back through the
    # rounding, which rounds it to bf16 in turn (see ``_through_rounding``)
    return {k: x.astype(jnp.bfloat16).astype(jnp.float32) for k, x in w.items()}


#: the gradient's way back through ``_rounded``: each sequence's gradient is
#: taken for the rounded weights and the sum over the batch rounded once, as
#: differentiating the whole batch's loss through the rounding rounds it
_through_rounding = jax.jit(_rounded, donate_argnums=(0,))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_forward(a: Arch, precision: str, w: dict, h):
    return layer_apply(a, _rounded(w), h, precision)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_backward(a: Arch, precision: str, w: dict, h, g):
    """(gradient for the rounded weights, input gradient) of one layer,
    recomputed from its input."""
    _, vjp = jax.vjp(lambda w, h: layer_apply(a, w, h, precision), _rounded(w), h)
    return vjp(g)


@jax.jit
def _embed(w, tokens):
    return w.astype(jnp.bfloat16).astype(jnp.float32)[tokens]


@jax.jit
def _embed_backward(w, tokens, g):
    """The gradient for the rounded embedding of one sequence's ``tokens``."""
    return jnp.zeros_like(w).at[tokens].add(g)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(a: Arch, precision: str, top: dict, h, targets, n_valid):
    """One sequence's share of the batch loss (its CE and z-loss terms over the
    batch's ``n_valid`` targets), with its gradients for the rounded head and
    for h."""
    def share(top, h):
        logits = _logits(a, top, h, precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[:, None], axis=-1)[:, 0]
        valid = (targets >= 0).astype(jnp.float32)
        return (jnp.sum((lse - gold) * valid) + Z_COEF * jnp.sum(lse * lse * valid)) / n_valid

    return jax.value_and_grad(share, argnums=(0, 1))(_rounded(top), h)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc: dict, g: dict) -> dict:
    return {k: acc[k] + g[k] for k in acc}


@jax.jit
def _sumsq(tree: dict) -> dict:
    return {k: jnp.sum(jnp.square(x)) for k, x in tree.items()}


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2, 3))
def adamw_update(opt: AdamW, master: dict, m: dict, v: dict, g: dict, scale, step, lr):
    """AdamW on some of the leaves: ``scale`` clips by the global norm, which
    the caller takes over all of them; bias correction, decoupled decay."""
    g = {k: x * scale for k, x in g.items()}
    c1, c2 = 1 - opt.b1 ** step, 1 - opt.b2 ** step
    m = {k: opt.b1 * m[k] + (1 - opt.b1) * g[k] for k in g}
    v = {k: opt.b2 * v[k] + (1 - opt.b2) * g[k] ** 2 for k in g}
    master = {
        k: master[k] - lr * ((m[k] / c1) / (jnp.sqrt(v[k] / c2) + opt.eps)
                             + opt.weight_decay * master[k])
        for k in g
    }
    return master, m, v


def clip_scale(opt: AdamW, gnorm: float) -> float:
    return min(1.0, opt.clip_norm / max(gnorm, 1e-12))


def train_reference(a: Arch, seed: int, batches, opt: AdamW, precision: str = "f32",
                    devices=None):
    """Follow ``len(batches)`` steps from the ``stacked``-rule weights.

    The layers are split over ``devices`` (default: JAX's first) in contiguous
    groups of equal size, the embedding on the first and the final norm and
    head on the last: each device holds its leaves' f32 master, AdamW moments
    and gradient sum.  A step runs every sequence forward layer by layer,
    keeping each layer's input on its device, then every sequence backward
    layer by layer, recomputing each layer from its input, and adds each
    sequence's gradient into the sums.  There is no pipeline schedule: the
    devices overlap only as far as JAX's asynchronous dispatch lets them.

    Returns the loss of each step, the per-leaf norm of the first gradient as
    the optimizer takes it (after clipping), and the per-leaf norm of the
    master weights' change after the last step.
    """
    devices = list(devices) if devices else jax.devices()[:1]
    owner = {i: devices[i * len(devices) // a.n_layers] for i in range(a.n_layers)}
    parts = {("layer", i): (owner[i], _STACKED) for i in range(a.n_layers)}
    parts["embed"] = (devices[0], ("embed.tokens",))
    parts["head"] = (devices[-1], ("final_norm", "lm_head"))

    def per_part(fn):
        """{part: {leaf: fn(part, leaf, its initial value on the part's
        device)}}, one whole leaf drawn at a time."""
        out = {p: {} for p in parts}
        for name, stacked, _ in LEAVES:
            w = stacked_leaf(a, seed, name)
            for p, (dev, names) in parts.items():
                if name in names:
                    out[p][name] = fn(p, name, jax.device_put(w[p[1]] if stacked else w, dev))
            del w
        return out

    with jax.default_matmul_precision("highest"):
        master = per_part(lambda p, name, w: w)
        m = {p: {k: jnp.zeros_like(x) for k, x in leaves.items()} for p, leaves in master.items()}
        v = {p: {k: jnp.zeros_like(x) for k, x in leaves.items()} for p, leaves in master.items()}
        losses, first_grad = [], None
        for step, (tokens, targets) in enumerate(batches):
            n_valid = float(max(int((np.asarray(targets) >= 0).sum()), 1))
            gsum = {p: None for p in parts}

            def accumulate(p, g):
                gsum[p] = g if gsum[p] is None else _add(gsum[p], g)

            # forward, every sequence; the head's gradient seeds the backward
            stash, seeds, shares = {}, {}, []
            for b in range(len(tokens)):
                tok = jax.device_put(jnp.asarray(tokens[b]), devices[0])
                h = _embed(master["embed"]["embed.tokens"], tok)
                for i in range(a.n_layers):
                    h = stash[b, i] = jax.device_put(h, owner[i])
                    h = _layer_forward(a, precision, master["layer", i], h)
                tgt = jax.device_put(jnp.asarray(targets[b]), devices[-1])
                share, (g_top, seeds[b]) = _head(a, precision, master["head"],
                                                 jax.device_put(h, devices[-1]), tgt, n_valid)
                shares.append(share)
                accumulate("head", g_top)
            # backward, every sequence
            for b in range(len(tokens)):
                g = seeds.pop(b)
                for i in reversed(range(a.n_layers)):
                    gw, g = _layer_backward(a, precision, master["layer", i], stash.pop((b, i)),
                                            jax.device_put(g, owner[i]))
                    accumulate(("layer", i), gw)
                tok = jax.device_put(jnp.asarray(tokens[b]), devices[0])
                accumulate("embed", {"embed.tokens": _embed_backward(
                    master["embed"]["embed.tokens"], tok, jax.device_put(g, devices[0]))})
            losses.append(float(sum(float(x) for x in shares)))

            gsum = {p: _through_rounding(g) for p, g in gsum.items()}
            sq = {p: {k: float(x) for k, x in _sumsq(g).items()} for p, g in gsum.items()}
            scale = clip_scale(opt, math.sqrt(sum(x for leaves in sq.values()
                                                  for x in leaves.values())))
            if first_grad is None:
                first_grad = {
                    name: scale * math.sqrt(sum(leaves[name] for leaves in sq.values()
                                                if name in leaves))
                    for name, _, _ in LEAVES
                }
            for p in parts:
                master[p], m[p], v[p] = adamw_update(opt, master[p], m[p], v[p], gsum.pop(p),
                                                     scale, step + 1, lr_at(opt, step))
        del m, v
        change = per_part(lambda p, name, w: float(diff_norm(master[p][name], w)) ** 2)
    return {
        "losses": losses,
        "grad_norms": first_grad,
        "change_norms": {name: math.sqrt(sum(leaves[name] for leaves in change.values()
                                              if name in leaves))
                         for name, _, _ in LEAVES},
    }


# ---------------------------------------------------------------------------
# Serving: the gap of each served token below the reference's best logit
# ---------------------------------------------------------------------------

def served_token_gaps(a: Arch, seed: int, seqs: list[tuple[list[int], list[int]]],
                      pad_to: int, precision: str = "f32") -> dict:
    """For each (prompt, served tokens) pair, run the ``per_layer``-rule model
    over ``prompt + served[:-1]`` and read, at every served position, how far
    the served token's logit lies below the best logit.

    With ``precision="fp8"`` the fp8 model picks its own top token at each
    position and the gap is read for that token in the float32 logits.
    Returns the widest gap and the number of served tokens compared.
    """
    with jax.default_matmul_precision("highest"):
        top = top_weights(a, seed)
        hs = []
        for prompt, served in seqs:
            ids = np.zeros(pad_to, np.int32)
            seq = list(prompt) + list(served[:-1])
            ids[: len(seq)] = seq
            hs.append(top["embed.tokens"][jnp.asarray(ids)])
        layer_f32 = jax.jit(functools.partial(layer_apply, a, precision="f32"))
        layer_low = jax.jit(functools.partial(layer_apply, a, precision=precision))
        lows = list(hs)
        for i in range(a.n_layers):
            w = layer_weights(a, seed, i)
            hs = [layer_f32(w, h) for h in hs]
            if precision != "f32":
                lows = [layer_low(w, h) for h in lows]
            del w
        head = jax.jit(functools.partial(_logits, a), static_argnums=(2,))
        widest, n = 0.0, 0
        for (prompt, served), h, low in zip(seqs, hs, lows):
            at = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
            ref = np.asarray(head(top, h[at], "f32"))
            if precision == "f32":
                pick = np.asarray(served)
            else:
                pick = np.argmax(np.asarray(head(top, low[at], precision)), axis=-1)
            gaps = ref.max(axis=-1) - ref[np.arange(len(served)), pick]
            widest = max(widest, float(gaps.max()))
            n += len(served)
    return {"widest_gap": widest, "tokens": n}
