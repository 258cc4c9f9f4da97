"""Weights made by the benchmark from the seed, on the device.

Every value of every leaf comes from a key that names (seed, leaf,
member, layer), so the program's whole stack is made in one jitted call
and the plain reference can make any one slice again, bit for bit,
without keeping or reading what the program holds.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key from any whole number (more than 32 bits hold)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                              seed // 2 ** 31)


def slice_key(seed: int, leaf: int, member: int, layer: int = 0):
    k = jax.random.fold_in(base_key(seed), leaf)
    k = jax.random.fold_in(k, member)
    return jax.random.fold_in(k, layer)


def draw(key, shape, std: float, mean: float, dtype):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


# -- dense decoder LM in the program's layout ---------------------------------
# (path in the program's param tree, shape, std, mean, per layer, dtype kind)
# dtype kind "w" is the served dtype; "f32" stays float32 (norm scales).


def lm_leaves(m: dict) -> list:
    d, H, Hkv, hd, ff, V = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                            m["head_dim"], m["d_ff"], m["vocab_size"])
    s = 1.0 / math.sqrt(d)
    return [
        (("embed",), (V, d), 0.02, 0.0, False, "w"),
        (("head",), (V, d), s, 0.0, False, "w"),
        (("final_norm", "norm_scale"), (d,), 0.1, 1.0, False, "f32"),
        (("norm_mix", "norm_scale"), (d,), 0.1, 1.0, True, "f32"),
        (("attn", "w_q"), (d, H * hd), s, 0.0, True, "w"),
        (("attn", "w_k"), (d, Hkv * hd), s, 0.0, True, "w"),
        (("attn", "w_v"), (d, Hkv * hd), s, 0.0, True, "w"),
        (("attn", "w_o"), (H * hd, d), 1.0 / math.sqrt(H * hd), 0.0, True,
         "w"),
        (("norm_ffn", "norm_scale"), (d,), 0.1, 1.0, True, "f32"),
        (("mlp", "w_gate"), (d, ff), s, 0.0, True, "w"),
        (("mlp", "w_up"), (d, ff), s, 0.0, True, "w"),
        (("mlp", "w_down"), (ff, d), 1.0 / math.sqrt(ff), 0.0, True, "w"),
    ]


def _dtype(kind: str, m: dict):
    return jnp.float32 if kind == "f32" else jnp.dtype(m["dtype"])


def lm_slice(m: dict, seed: int, leaf: int, member: int, layer: int):
    """One layer's (or one whole, for non-layer leaves) value of a leaf
    for one member, in the served dtype."""
    _, shape, std, mean, _, kind = lm_leaves(m)[leaf]
    return draw(slice_key(seed, leaf, member, layer), shape, std, mean,
                _dtype(kind, m))


def lm_params(m: dict, seed: int) -> dict:
    """The K-member stack {embed, head, final_norm, segments: [{slot_0:
    ...}]} with leading (K,) and, for layer leaves, (K, n_layers, ...)
    axes: the layout `jax.vmap(transformer.init)` produces."""
    K, L = m["members"], m["n_layers"]

    @partial(jax.jit, static_argnums=())
    def make(key):
        top, layer = {}, {}
        for i, (path, shape, std, mean, per_layer, kind) in enumerate(
                lm_leaves(m)):
            dt = _dtype(kind, m)
            ki = jax.random.fold_in(key, i)

            def one(mm, ll, ki=ki, shape=shape, std=std, mean=mean, dt=dt):
                k = jax.random.fold_in(jax.random.fold_in(ki, mm), ll)
                return draw(k, shape, std, mean, dt)

            if per_layer:
                val = jax.vmap(lambda mm: jax.vmap(
                    lambda ll: one(mm, ll))(jnp.arange(L)))(jnp.arange(K))
                dst = layer
            else:
                val = jax.vmap(lambda mm: one(mm, 0))(jnp.arange(K))
                dst = top
            for p in path[:-1]:
                dst = dst.setdefault(p, {})
            dst[path[-1]] = val
        top["segments"] = [{"slot_0": layer}]
        return top

    return make(base_key(seed))


# -- NiN (the paper's CIFAR network) and its image data -----------------------


def nin_leaves(m: dict) -> list:
    """(name, shape, std) per leaf in the program's naming: conv_<i>_w
    (k, k, cin, cout) and bias_<i> for each conv of the layer list, then
    the 1x1 classifier conv_out_w / bias_out."""
    out, ch = [], m["channels"]
    for i, (kind, cout, k, _s) in enumerate(m["layers"]):
        if kind != "conv":
            continue
        out.append((f"conv_{i}_w", (k, k, ch, cout),
                    1.0 / (k * math.sqrt(ch))))
        out.append((f"bias_{i}", (cout,), m["bias_std"]))
        ch = cout
    out.append(("conv_out_w", (1, 1, ch, m["n_classes"]),
                1.0 / math.sqrt(ch)))
    out.append(("bias_out", (m["n_classes"],), m["bias_std"]))
    return out


def nin_member(m: dict, seed: int, member: int) -> dict:
    """One member's float32 parameters, made again from the seed."""
    return {name: draw(slice_key(seed, i, member), shape, std, 0.0,
                       jnp.float32)
            for i, (name, shape, std) in enumerate(nin_leaves(m))}


def nin_params(m: dict, seed: int) -> dict:
    """The K-member stack (leading (K,) axis), in one jitted call."""
    K = m["members"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, std) in enumerate(nin_leaves(m)):
            ki = jax.random.fold_in(key, i)
            out[name] = jax.vmap(lambda mm, ki=ki, shape=shape, std=std: draw(
                jax.random.fold_in(jax.random.fold_in(ki, mm), 0), shape,
                std, 0.0, jnp.float32))(jnp.arange(K))
        return out

    return make(base_key(seed))


DATA_LEAF = 10_000  # key offset of the data, apart from every weight leaf


def image_data(m: dict, seed: int, n: int, test: bool = False) -> dict:
    """{images (K, n, img, img, ch) f32, labels (K, n) i32} (or one
    (n, ...) set when test=True): class prototypes plus Gaussian noise,
    half of the images flipped left to right, made on the device."""
    K, C, img, ch = m["members"], m["n_classes"], m["img"], m["channels"]
    lead = (n,) if test else (K, n)

    @jax.jit
    def make(key):
        kp, kl, kn, kf = jax.random.split(key, 4)
        protos = 0.8 * jax.random.normal(kp, (C, img, img, ch))
        labels = jax.random.randint(kl, lead, 0, C)
        x = protos[labels] + 0.35 * jax.random.normal(
            kn, lead + (img, img, ch))
        flip = jax.random.bernoulli(kf, 0.5, lead)
        x = jnp.where(flip[..., None, None, None], x[..., ::-1, :], x)
        return {"images": x.astype(jnp.float32),
                "labels": labels.astype(jnp.int32)}

    return make(jax.random.fold_in(base_key(seed), DATA_LEAF + int(test)))


def token_data(m: dict, seed: int, n: int, test: bool = False) -> dict:
    """{tokens, labels} (K, n, seq_len) int32 (or one (n, seq_len) set
    when test=True): sequences of seq_len + 1 ids drawn uniformly from
    the configuration's vocabulary, labels the next id, made on the
    device."""
    lead = (n,) if test else (m["members"], n)

    @jax.jit
    def make(key):
        seq = jax.random.randint(key, lead + (m["seq_len"] + 1,), 0,
                                 m["vocab_size"], jnp.int32)
        return {"tokens": seq[..., :-1], "labels": seq[..., 1:]}

    return make(jax.random.fold_in(base_key(seed), DATA_LEAF + 2 + int(test)))
