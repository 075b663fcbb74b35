"""Plain reference of a dense decoder-only language model.

Straightforward ``jax.numpy`` in float32, written from the published
descriptions (GPT-2/nanoGPT; Llama-style GQA with RoPE, RMSNorm and
SwiGLU for Granite) and imports nothing of the program. The
configuration file's ``arch`` block says which pieces a model has.

It defines, for the benchmark:

- ``leaf_specs(arch)``: every parameter with its shape and the norm its
  linear minimisation oracle uses (hidden matrices ``spectral``,
  embeddings and vectors ``sign``; vectors are sent uncompressed);
- ``init_params(arch, key)``: the weights of a run, made from the seed
  on the device in the dtype they are trained in;
- ``loss(params, batch, arch, quant, mask)``: mean next-token
  cross-entropy of one worker's batch;
- ``train_flops_per_token(arch, seq)``: the operations forward and
  backward require per token.

The parameter layout (paths and ``[in, out]`` matrices stacked over
layers) is the program's; the harness checks it against the program's
abstract parameters before a run.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import flops


def _norm_paths(arch: dict, prefix: str) -> list[str]:
    names = [prefix + "_w"]
    if arch["norm"] == "layernorm":
        names.append(prefix + "_b")
    return names


def leaf_specs(arch: dict) -> dict:
    """path -> {"shape", "kind", "stack"}; ``kind`` is
    ``spectral`` (a stack of [in, out] matrices), ``sign`` (embeddings)
    or ``vector`` (norm weights and biases: sign LMO, no compression)."""
    d, L = arch["d_model"], arch["n_layers"]
    hd = d // arch["n_heads"]
    q_out = arch["n_heads"] * hd
    kv_out = arch["n_kv_heads"] * hd
    ff = arch["d_ff"]
    specs = {"embed": {"shape": (arch["vocab"], d), "kind": "sign"}}
    if arch["rope"] == "learned":
        specs["pos_embed"] = {"shape": (arch["max_position"], d),
                              "kind": "sign"}
    for p in _norm_paths(arch, "final_ln"):
        specs[p] = {"shape": (d,), "kind": "vector"}
    blk = "dense_blocks/"
    for ln in ("ln1", "ln2"):
        for p in _norm_paths(arch, blk + ln):
            specs[p] = {"shape": (L, d), "kind": "vector"}
    mats = {"attn/wq": (d, q_out), "attn/wk": (d, kv_out),
            "attn/wv": (d, kv_out), "attn/wo": (q_out, d)}
    if arch["act"] == "swiglu":
        mats["mlp/w_gate"] = (d, ff)
    mats["mlp/w_up"] = (d, ff)
    mats["mlp/w_down"] = (ff, d)
    for name, (fi, fo) in mats.items():
        specs[blk + name] = {"shape": (L, fi, fo), "kind": "spectral"}
    for s in specs.values():
        # leading dims that stack independent layers
        s["stack"] = {"spectral": len(s["shape"]) - 2, "sign": 0,
                      "vector": len(s["shape"]) - 1}[s["kind"]]
    return specs


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    """Nested dict -> {path: leaf}."""
    out = {}
    for k, v in tree.items():
        p = prefix + k
        if isinstance(v, dict):
            out.update(flatten(v, p + "/"))
        else:
            out[p] = v
    return out


def init_params(arch: dict, key: jax.Array) -> dict:
    """Seeded weights in the model's dtype, one jitted call: matrices
    N(0, 1/fan_in), embeddings N(0, 0.02^2), norm weights 1, biases 0."""
    specs = leaf_specs(arch)
    dtype = jnp.dtype(arch["dtype"])

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, s) in enumerate(sorted(specs.items())):
            k = jax.random.fold_in(key, i)
            shape = s["shape"]
            if s["kind"] == "spectral":
                w = jax.random.normal(k, shape, jnp.float32) \
                    / math.sqrt(shape[-2])
            elif s["kind"] == "sign":
                w = jax.random.normal(k, shape, jnp.float32) * 0.02
            elif path.endswith("_b"):
                w = jnp.zeros(shape, jnp.float32)
            else:
                w = jnp.ones(shape, jnp.float32)
            flat[path] = w.astype(dtype)
        return _nest(flat)

    return make(key)


# ------------------------------------------------------------- forward


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _norm(arch, p, prefix, x):
    if arch["norm"] == "layernorm":
        return _layer_norm(x, p[prefix + "_w"], p[prefix + "_b"],
                           arch["norm_eps"])
    return _rms_norm(x, p[prefix + "_w"], arch["norm_eps"])


def _rope(x, base):
    """Rotary embedding, rotate-half convention; x [B, S, H, D]."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, chunk: int):
    """Causal softmax attention, computed a block of queries at a time.
    q [B, S, H, D]; k, v [B, S, KVH, D]; query head h reads key/value
    head h // (H / KVH)."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    chunk = min(chunk, s)
    nq = s // chunk
    qc = q.reshape(b, nq, chunk, h, d).swapaxes(0, 1)
    scale = 1.0 / math.sqrt(d)

    @jax.checkpoint
    def block(i, qb):
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        qpos = i * chunk + jnp.arange(chunk)
        mask = jnp.arange(s)[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(lambda a: block(*a), (jnp.arange(nq), qc))
    return out.swapaxes(0, 1).reshape(b, s, h, d)


def loss(params: dict, batch: dict, arch: dict, quant=None,
         mask=None, chunk: int = 1024) -> jax.Array:
    """Mean cross-entropy of one worker's batch {"tokens", "labels"}
    [B, S] under float32 ``params``. ``quant`` rounds the operands of
    every matrix product (the control's lower precision); ``mask``
    [B, S] keeps a subset of the tokens in the mean."""
    qz = quant if quant is not None else (lambda a: a)

    def mm(a, w):
        return jnp.matmul(qz(a), qz(w))

    toks = batch["tokens"]
    b, s = toks.shape
    hd = arch["d_model"] // arch["n_heads"]
    x = params["embed"][toks]
    if arch["rope"] == "learned":
        x = x + params["pos_embed"][jnp.arange(s)][None]

    @jax.checkpoint
    def layer(x, p):
        h = _norm(arch, p, "ln1", x)
        a = p["attn"]
        q = mm(h, a["wq"]).reshape(b, s, arch["n_heads"], hd)
        k = mm(h, a["wk"]).reshape(b, s, arch["n_kv_heads"], hd)
        v = mm(h, a["wv"]).reshape(b, s, arch["n_kv_heads"], hd)
        if arch["rope"] == "rope":
            q, k = _rope(q, arch["rope_base"]), _rope(k, arch["rope_base"])
        o = _attention(qz(q), qz(k), qz(v), chunk)
        x = x + mm(o.reshape(b, s, -1), a["wo"])
        h = _norm(arch, p, "ln2", x)
        m = p["mlp"]
        if arch["act"] == "swiglu":
            u = jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"])
        else:
            u = _gelu_tanh(mm(h, m["w_up"]))
        return x + mm(u, m["w_down"]), None

    x, _ = jax.lax.scan(layer, x, params["dense_blocks"])
    x = _norm(arch, params, "final_ln", x)
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    chunk = min(chunk, s)
    n = s // chunk
    xc = x.reshape(b, n, chunk, -1).swapaxes(0, 1)
    lc = batch["labels"].reshape(b, n, chunk).swapaxes(0, 1)
    mc = mask.reshape(b, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def xent(tot, xs):
        h, lbl, msk = xs
        logits = mm(h, params["embed"].T)
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, lbl[..., None], -1)[..., 0]
        return tot + jnp.sum(nll * msk), None

    tot, _ = jax.lax.scan(xent, jnp.zeros((), jnp.float32), (xc, lc, mc))
    return tot / jnp.sum(mask)


def train_flops_per_token(arch: dict, seq: int) -> float:
    """Forward and backward operations per token: three times the
    forward's matrix products (every hidden matrix, the tied output
    head, and attention's scores and values over the whole sequence,
    as the usual MFU accounting counts them). Recomputation under remat
    is not counted, nor the optimizer."""
    specs = leaf_specs(arch)
    d = arch["d_model"]
    hidden = sum(math.prod(s["shape"]) for s in specs.values()
                 if s["kind"] == "spectral")
    attn_width = arch["n_heads"] * (d // arch["n_heads"])
    fwd = (flops.matmul_flops(1, hidden, 1)
           + flops.matmul_flops(1, d, arch["vocab"])
           + arch["n_layers"] * flops.attention_flops(seq, attn_width))
    return 3.0 * fwd
