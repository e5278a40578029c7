"""Plain reference of the dense GPT block that the gpt-2b configurations
state, and of AdamW, in straightforward ``jax.numpy``.

It imports nothing of the program.  It follows the configuration file:
pre-norm blocks of RMSNorm, causal multi-head attention with rotary
position embeddings on the two halves of each head, and a tanh-GELU MLP; a
final RMSNorm and an untied LM head; token-mean cross-entropy.  AdamW clips
the global gradient norm, corrects both moments for bias, warms the rate up
linearly and decays it on a cosine, and decays every parameter but the ones
the configuration exempts.

The weights are made here, from the seed, in one jitted call: the entries
hand the same arrays to the program, so the reference never takes weights
the program made.  Gradients are summed over blocks of rows, with each
layer rematerialised, so that the reference fits beside nothing else once
the program's state is freed.

``mode`` selects the precision: ``"highest"`` is the reference (float32
matmuls at full precision); ``"bf16"`` and ``"fp8"`` are the controls, the
same arithmetic in the precision below the one a configuration states.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -2.0 ** 30


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def param_shapes(arch: dict) -> dict:
    """Shapes of the weights, per-layer weights stacked on a leading axis."""
    d, f, V, L = (arch["d_model"], arch["d_ff"], arch["vocab_size"],
                  arch["n_layers"])
    hd = arch["d_model"] // arch["n_heads"]
    q, kv = arch["n_heads"] * hd, arch["n_kv_heads"] * hd
    return {
        "embed": (V, d),
        "blocks": {
            "ln1": (L, d),
            "attn": {"wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
                     "wo": (L, q, d)},
            "ln2": (L, d),
            "mlp": {"w_up": (L, d, f), "w_down": (L, f, d)},
        },
        "final_norm": (d,),
        "lm_head": (d, V),
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64 bits and more included."""
    key = jax.random.PRNGKey(0)
    for word in (seed >> 64, (seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF):
        key = jax.random.fold_in(key, np.uint32(word))
    return key


def init_params(arch: dict, key) -> dict:
    """Norm gains 1; embedding N(0, 0.02^2); every matrix N(0, 1/fan_in)."""
    shapes = param_shapes(arch)
    paths = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)[0]
    out = []
    for i, (path, shape) in enumerate(paths):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        if name in ("ln1", "ln2", "final_norm"):
            out.append(jnp.ones(shape, jnp.float32))
        elif name == "embed":
            out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
        else:
            out.append(jax.random.normal(k, shape, jnp.float32)
                       * shape[-2] ** -0.5)
    treedef = jax.tree_util.tree_structure(shapes, is_leaf=_is_shape)
    return jax.tree_util.tree_unflatten(treedef, out)


def leaf_norms(params: dict) -> jnp.ndarray:
    """L2 norm of every leaf, each layer's slice of a stacked leaf apart,
    in the order of :func:`leaf_names`."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        x = x.astype(jnp.float32)
        if path[0].key == "blocks":
            out.append(jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)),
                                        axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)


def leaf_names(arch: dict) -> List[str]:
    names = []
    shapes = param_shapes(arch)
    for path, _ in jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=_is_shape)[0]:
        name = "/".join(p.key for p in path)
        if path[0].key == "blocks":
            names += [f"{name}/{l}" for l in range(arch["n_layers"])]
        else:
            names.append(name)
    return names


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fp8(x):
    """Per-tensor scaled float8 (e4m3) rounding of a matmul operand."""
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-30) / 448.0
    q = (x.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * s).astype(x.dtype)


def _mm(x, w, mode):
    if mode == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum("...i,io->...o", x, w.astype(x.dtype))


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    T, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _layer(arch, mode, h, p):
    B, T, d = h.shape
    H, KV = arch["n_heads"], arch["n_kv_heads"]
    hd = d // H
    eps = arch["norm_eps"]
    x = _rms(h, p["ln1"], eps)
    q = _mm(x, p["attn"]["wq"], mode).reshape(B, T, H, hd)
    k = _mm(x, p["attn"]["wk"], mode).reshape(B, T, KV, hd)
    v = _mm(x, p["attn"]["wv"], mode).reshape(B, T, KV, hd)
    q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal[None, None], s, NEG_INF)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                   v.astype(jnp.float32)).astype(h.dtype)
    h = h + _mm(a.reshape(B, T, d), p["attn"]["wo"], mode)
    x = _rms(h, p["ln2"], eps)
    u = jax.nn.gelu(_mm(x, p["mlp"]["w_up"], mode), approximate=True)
    return h + _mm(u, p["mlp"]["w_down"], mode)


def loss_sum(arch: dict, mode: str, params: dict, tokens, labels):
    """Sum over the rows' tokens of the next-token cross-entropy."""
    dt = jnp.float32 if mode == "highest" else jnp.bfloat16
    if mode == "fp8" or mode == "bf16":
        params = jax.tree.map(lambda x: x.astype(dt), params)
    h = params["embed"][tokens].astype(dt)
    body = jax.checkpoint(lambda c, p: (_layer(arch, mode, c, p), None))
    h, _ = jax.lax.scan(body, h, params["blocks"])
    logits = _mm(_rms(h, params["final_norm"], arch["norm_eps"]),
                 params["lm_head"], mode).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - ll)


# ---------------------------------------------------------------------------
# AdamW, three steps, and what the comparison reads
# ---------------------------------------------------------------------------


def lr_at(opt: dict, step: int) -> float:
    """Rate of update ``step`` (0-based): linear warm-up, cosine decay."""
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    frac = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * frac


def _spec(shape, n):
    """Shard the largest axis that the devices divide; else replicate."""
    for ax in sorted(range(len(shape)), key=lambda a: -shape[a]):
        if shape[ax] % n == 0:
            return P(*[("x" if a == ax else None) for a in range(len(shape))])
    return P()


class Reference:
    """AdamW training of the plain model on ``devices``, by blocks of
    ``rows_per_block`` rows, each block's rows spread over the devices."""

    def __init__(self, arch: dict, opt: dict, devices: Sequence,
                 rows_per_block: int, mode: str = "highest"):
        self.arch, self.opt, self.mode = arch, opt, mode
        self.rows = rows_per_block
        self.mesh = Mesh(np.asarray(devices), ("x",))
        n = len(devices)
        if rows_per_block % n:
            raise ValueError(f"{rows_per_block} rows per block over {n} "
                             "devices")
        shapes = param_shapes(arch)
        self.p_sh = jax.tree.map(
            lambda s: NamedSharding(self.mesh, _spec(s, n)), shapes,
            is_leaf=_is_shape)
        self.row_sh = NamedSharding(self.mesh, P("x", None))
        rep = NamedSharding(self.mesh, P())
        decay = self._decay_mask()
        self.init = jax.jit(lambda k: init_params(arch, k),
                            out_shardings=self.p_sh)
        self._acc = jax.jit(self._acc_fn, donate_argnums=(1, 2),
                            out_shardings=(self.p_sh, rep))
        self._zeros = jax.jit(
            lambda: jax.tree.map(lambda s: jnp.zeros(s, jnp.float32), shapes,
                                 is_leaf=_is_shape),
            out_shardings=self.p_sh)
        self._adam = jax.jit(
            lambda *a: self._adam_fn(decay, *a), donate_argnums=(0, 1, 2),
            out_shardings=(self.p_sh, self.p_sh, self.p_sh, rep))
        self._delta = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))

    def _decay_mask(self) -> dict:
        exempt = set(self.opt.get("no_decay", ()))
        return jax.tree_util.tree_map_with_path(
            lambda path, _: path[-1].key not in exempt,
            param_shapes(self.arch), is_leaf=_is_shape)

    def _acc_fn(self, params, g_acc, l_acc, tokens, labels):
        l, g = jax.value_and_grad(
            lambda p: loss_sum(self.arch, self.mode, p, tokens, labels))(params)
        return (jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g_acc, g),
                l_acc + l)

    def _adam_fn(self, decay, params, m, v, g, n, lr, step):
        o = self.opt
        g = jax.tree.map(lambda x: x / n, g)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, o["grad_clip"] / (gn + 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        b1, b2 = o["beta1"], o["beta2"]
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step

        def upd(p, a, b, dk):
            delta = (a / c1) / (jnp.sqrt(b / c2) + o["eps"])
            if dk:
                delta = delta + o["weight_decay"] * p
            return p - lr * delta

        params = jax.tree.map(upd, params, m, v, decay)
        return params, m, v, leaf_norms(g)

    def train(self, seed: int, batches: List[dict], *,
              rows: Optional[int] = None) -> Dict[str, np.ndarray]:
        """AdamW steps from the seed's weights over ``batches``; the loss of
        each step, each leaf's norm of the first clipped gradient, and each
        leaf's norm of the weights' change after the last step.  ``rows``
        keeps only a batch's first rows (a fault: part of the batch left
        out, the mean taken over the rest)."""
        precision = ("highest" if self.mode == "highest"
                     else jax.config.jax_default_matmul_precision)
        with jax.default_matmul_precision(precision):
            return self._train(seed, batches, rows)

    def _train(self, seed, batches, rows):
        key = seed_key(seed)
        params = self.init(key)
        m, v = self._zeros(), self._zeros()
        losses, first_grad = [], None
        for k, b in enumerate(batches):
            tok, lab = b["tokens"], b["labels"]
            if rows is not None:
                tok, lab = tok[:rows], lab[:rows]
            g, l = self._zeros(), jnp.zeros((), jnp.float32)
            for r in range(0, tok.shape[0], self.rows):
                t = jax.device_put(tok[r:r + self.rows], self.row_sh)
                y = jax.device_put(lab[r:r + self.rows], self.row_sh)
                g, l = self._acc(params, g, l, t, y)
            params, m, v, gnorms = self._adam(
                params, m, v, g, jnp.float32(tok.size),
                jnp.float32(lr_at(self.opt, k)), jnp.float32(k + 1))
            losses.append(float(l) / tok.size)
            if first_grad is None:
                first_grad = np.asarray(gnorms)
            del g
        del m, v
        p0 = self.init(key)
        delta = np.asarray(self._delta(params, p0))
        return {"loss": np.asarray(losses), "grad": first_grad,
                "delta": delta}
