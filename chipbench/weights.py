"""Seeded weights for a configuration's networks, made on the device in
one jitted call, in the layout the served model loads (one dict per
block; see ``repro.vision.zoo``).  The benchmark makes them, so the
plain reference and the program are given the same numbers and the
reference takes nothing the program made.

Convolutions and dense layers are He-normal; BatchNorm statistics and
affine parameters are drawn around the identity, so the folded
inference BatchNorm is exercised and not a no-op.  All leaves are cut
from one normal and one uniform draw, which keeps the call quick to
compile.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.netdef import se_channels, spatial_out_channels, walk

# (path, shape, kind, a, b): kind "normal" gives a * N(0, 1);
# "uniform" gives U(a, b)
Leaf = Tuple[tuple, tuple, str, float, float]


def prng_key(seed: int, stream: int = 0) -> jax.Array:
    """A JAX key from any non-negative seed, wider than 32 bits too."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)


def _bn(path, c) -> List[Leaf]:
    return [(path + ("scale",), (c,), "uniform", 0.75, 1.25),
            (path + ("bias",), (c,), "normal", 0.1, 0),
            (path + ("mean",), (c,), "normal", 0.1, 0),
            (path + ("var",), (c,), "uniform", 0.75, 1.25)]


def _dense(path, cin, cout) -> List[Leaf]:
    return [(path + ("w",), (cin, cout), "normal", np.sqrt(1.0 / cin), 0),
            (path + ("b",), (cout,), "normal", 0.01, 0)]


def _he(path, shape, fan_in) -> Leaf:
    return (path, shape, "normal", float(np.sqrt(2.0 / fan_in)), 0)


def leaves(net: dict) -> List[Leaf]:
    """Every parameter of one network: its place, shape and draw."""
    v = net["variant"]
    out: List[Leaf] = []
    for st in walk(net):
        b, x, y, i = st.block, st.x, st.y, (st.index,)
        t = b["type"]
        if t in ("stem", "conv"):
            k = b["kernel"]
            shape = (x.c, y.c) if t == "conv" and k == 1 else (k, k, x.c, y.c)
            out += [_he(i + ("w",), shape, k * k * x.c)] + _bn(i + ("bn",), y.c)
        elif t == "mbconv":
            e, k = b["exp"], b["kernel"]
            if e != x.c:
                out += [_he(i + ("expand",), (x.c, e), x.c)]
                out += _bn(i + ("bn0",), e)
            if v == "depthwise":
                out += [_he(i + ("sp", "dw"), (k, k, e), k * k)]
            else:
                c_r = e if v == "fuse_full" else e // 2
                c_c = e if v == "fuse_full" else e - c_r
                out += [_he(i + ("sp", "row"), (k, c_r), k),
                        _he(i + ("sp", "col"), (k, c_c), k)]
            c_sp = spatial_out_channels(v, e)
            out += _bn(i + ("bn1",), c_sp)
            if b["se"]:
                cr = se_channels(c_sp)
                out += _dense(i + ("se", "reduce"), c_sp, cr)
                out += _dense(i + ("se", "expand"), cr, c_sp)
            out += [_he(i + ("project",), (c_sp, y.c), c_sp)]
            out += _bn(i + ("bn2",), y.c)
        else:
            c = x.c
            if b.get("hidden"):
                out += _dense(i + ("hidden",), c, b["hidden"])
                c = b["hidden"]
            out += _dense(i + ("fc",), c, y.c)
    return out


def _assemble(specs: List[Leaf], n_blocks: int, normal, uniform) -> list:
    params: list = [{} for _ in range(n_blocks)]
    pos = {"normal": 0, "uniform": 0}
    for path, shape, kind, a, b in specs:
        size = int(np.prod(shape))
        src = normal if kind == "normal" else uniform
        flat = src[pos[kind]:pos[kind] + size]
        pos[kind] += size
        leaf = flat * a if kind == "normal" else a + (b - a) * flat
        node = params[path[0]]
        for p in path[1:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf.reshape(shape)
    return params


def make(config: dict, seed: int, device=None) -> list:
    """Params of every network of ``config``, from ``seed``, on
    ``device`` (default: JAX's first), in one jitted call."""
    nets = config["networks"]
    specs = [leaves(n) for n in nets]
    n_norm = sum(int(np.prod(s[1])) for sp in specs for s in sp
                 if s[2] == "normal")
    n_unif = sum(int(np.prod(s[1])) for sp in specs for s in sp
                 if s[2] == "uniform")

    def build(key):
        kn, ku = jax.random.split(key)
        normal = jax.random.normal(kn, (n_norm,), jnp.float32)
        uniform = jax.random.uniform(ku, (n_unif,), jnp.float32)
        out, pn, pu = [], 0, 0
        for sp, net in zip(specs, nets):
            m = sum(int(np.prod(s[1])) for s in sp if s[2] == "normal")
            u = sum(int(np.prod(s[1])) for s in sp if s[2] == "uniform")
            out.append(_assemble(sp, len(net["blocks"]),
                                 normal[pn:pn + m], uniform[pu:pu + u]))
            pn, pu = pn + m, pu + u
        return out

    key = prng_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.block_until_ready(jax.jit(build)(key))
