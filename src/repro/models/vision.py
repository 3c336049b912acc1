"""The paper's own evaluation families — ResNet, VGG, ViT — as
streaming-unit models (same protocol as :class:`repro.models.transformer.LM`)
so the cold-start pipeline benchmarks (Figs 9-14) run against the exact
model families the paper measured.

Unit granularity follows the PyTorch top-level-module decomposition the
paper pipelines over (stem / stages / head for CNNs; patch-embed /
encoder blocks / head for ViT).  Inference only — the paper's pipeline
optimizes loading, and its workload is a single `1x3x224x224` tensor.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.api import ArchConfig

PyTree = Any

RESNET_BLOCKS = {"resnet50": (3, 4, 6, 3), "resnet101": (3, 4, 23, 3),
                 "resnet152": (3, 8, 36, 3)}
VGG_STAGES = {"vgg11": (1, 1, 2, 2, 2), "vgg13": (2, 2, 2, 2, 2),
              "vgg16": (2, 2, 3, 3, 3), "vgg19": (2, 2, 4, 4, 4)}
VGG_CH = (64, 128, 256, 512, 512)
VIT = {"vit_b_16": (12, 768, 12, 3072, 16),
       "vit_b_32": (12, 768, 12, 3072, 32),
       "vit_l_16": (24, 1024, 16, 4096, 16)}


# ---------------------------------------------------------------------------
# primitive layers
# ---------------------------------------------------------------------------

def conv_init(key, kh, kw, cin, cout, dtype=jnp.float32):
    return layers.dense_init(key, (kh, kw, cin, cout), dtype,
                             fan_in=kh * kw * cin)


def conv2d(x, kernel, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def bn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32),
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def bn_apply(p, x, eps=1e-5):
    inv = jax.lax.rsqrt(p["var"] + eps)
    return (x - p["mean"]) * (inv * p["scale"]) + p["bias"]


def fc_init(key, cin, cout):
    return {"w": layers.dense_init(key, (cin, cout), jnp.float32,
                                   fan_in=cin),
            "b": jnp.zeros((cout,), jnp.float32)}


def fc_apply(p, x):
    return x @ p["w"] + p["b"]


def maxpool(x, window=3, stride=2):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "SAME")


# ---------------------------------------------------------------------------
# ResNet
# ---------------------------------------------------------------------------

def _bottleneck_init(key, cin, cmid, stride):
    ks = jax.random.split(key, 4)
    p = {"conv1": conv_init(ks[0], 1, 1, cin, cmid),
         "bn1": bn_init(cmid),
         "conv2": conv_init(ks[1], 3, 3, cmid, cmid),
         "bn2": bn_init(cmid),
         "conv3": conv_init(ks[2], 1, 1, cmid, cmid * 4),
         "bn3": bn_init(cmid * 4)}
    if stride != 1 or cin != cmid * 4:
        p["down"] = {"conv": conv_init(ks[3], 1, 1, cin, cmid * 4),
                     "bn": bn_init(cmid * 4)}
    return p


def _bottleneck_apply(p, x, stride):
    y = jax.nn.relu(bn_apply(p["bn1"], conv2d(x, p["conv1"])))
    y = jax.nn.relu(bn_apply(p["bn2"], conv2d(y, p["conv2"], stride)))
    y = bn_apply(p["bn3"], conv2d(y, p["conv3"]))
    if "down" in p:
        x = bn_apply(p["down"]["bn"], conv2d(x, p["down"]["conv"], stride))
    return jax.nn.relu(x + y)


def _resnet_units(cfg: ArchConfig):
    n_blocks = RESNET_BLOCKS[cfg.vision_variant]
    units: List[Tuple[str, Callable, Callable]] = []

    def stem_init(key):
        return {"conv": conv_init(key, 7, 7, 3, 64), "bn": bn_init(64)}

    def stem_apply(p, x):
        x = jax.nn.relu(bn_apply(p["bn"], conv2d(x, p["conv"], 2)))
        return maxpool(x)

    units.append(("stem", stem_init, stem_apply))

    cin = 64
    for si, nb in enumerate(n_blocks):
        cmid = 64 * (2 ** si)
        stride = 1 if si == 0 else 2
        cin_s = cin

        def mk_init(nb=nb, cin_s=cin_s, cmid=cmid, stride=stride):
            def f(key):
                ks = jax.random.split(key, nb)
                blocks = []
                ci = cin_s
                for b in range(nb):
                    blocks.append(_bottleneck_init(
                        ks[b], ci, cmid, stride if b == 0 else 1))
                    ci = cmid * 4
                return {"blocks": blocks}
            return f

        def mk_apply(nb=nb, stride=stride):
            def f(p, x):
                for b in range(nb):
                    x = _bottleneck_apply(p["blocks"][b], x,
                                          stride if b == 0 else 1)
                return x
            return f

        units.append((f"stage{si + 1}", mk_init(), mk_apply()))
        cin = cmid * 4

    def head_init(key):
        return {"fc": fc_init(key, cin, cfg.vocab_size)}

    def head_apply(p, x):
        return fc_apply(p["fc"], jnp.mean(x, axis=(1, 2)))

    units.append(("head", head_init, head_apply))
    return units


# ---------------------------------------------------------------------------
# VGG
# ---------------------------------------------------------------------------

def _vgg_units(cfg: ArchConfig):
    stages = VGG_STAGES[cfg.vision_variant]
    units: List[Tuple[str, Callable, Callable]] = []
    cin = 3
    for si, (nc, ch) in enumerate(zip(stages, VGG_CH)):
        cin_s = cin

        def mk_init(nc=nc, ch=ch, cin_s=cin_s):
            def f(key):
                ks = jax.random.split(key, nc)
                convs, ci = [], cin_s
                for c in range(nc):
                    convs.append(conv_init(ks[c], 3, 3, ci, ch))
                    ci = ch
                return {"convs": convs}
            return f

        def mk_apply(nc=nc):
            def f(p, x):
                for c in range(nc):
                    x = jax.nn.relu(conv2d(x, p["convs"][c]))
                return maxpool(x, 2, 2)
            return f

        units.append((f"stage{si + 1}", mk_init(), mk_apply()))
        cin = ch

    def head_init(key):
        ks = jax.random.split(key, 3)
        spatial = max(cfg.img_res // 32, 1)              # 5 maxpools of 2
        return {"fc1": fc_init(ks[0], 512 * spatial * spatial, 4096),
                "fc2": fc_init(ks[1], 4096, 4096),
                "fc3": fc_init(ks[2], 4096, cfg.vocab_size)}

    def head_apply(p, x):
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(fc_apply(p["fc1"], x))
        x = jax.nn.relu(fc_apply(p["fc2"], x))
        return fc_apply(p["fc3"], x)

    units.append(("head", head_init, head_apply))
    return units


# ---------------------------------------------------------------------------
# ViT
# ---------------------------------------------------------------------------

def _vit_units(cfg: ArchConfig):
    L, d, h, ff, patch = VIT[cfg.vision_variant]
    n_patch = (cfg.img_res // patch) ** 2
    units: List[Tuple[str, Callable, Callable]] = []

    def patch_init(key):
        k1, k2 = jax.random.split(key)
        return {"proj": conv_init(k1, patch, patch, 3, d),
                "pos": layers.embed_init(k2, (n_patch, d), jnp.float32)}

    def patch_apply(p, x):
        x = conv2d(x, p["proj"], stride=patch, padding="VALID")
        x = x.reshape(x.shape[0], -1, d)
        return x + p["pos"][None]

    units.append(("patch", patch_init, patch_apply))

    def blk_init(key):
        ks = jax.random.split(key, 6)
        dh = d // h
        return {
            "ln1": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
            "wq": layers.dense_init(ks[0], (d, h, dh), jnp.float32),
            "wk": layers.dense_init(ks[1], (d, h, dh), jnp.float32),
            "wv": layers.dense_init(ks[2], (d, h, dh), jnp.float32),
            "wo": layers.dense_init(ks[3], (h, dh, d), jnp.float32),
            "ln2": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
            "fc1": fc_init(ks[4], d, ff),
            "fc2": fc_init(ks[5], ff, d),
        }

    def blk_apply(p, x):
        y = layers.layernorm(x, p["ln1"]["scale"], p["ln1"]["bias"])
        q = jnp.einsum("bsd,dhk->bshk", y, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", y, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", y, p["wv"])
        s = jnp.einsum("bshk,bthk->bhst", q, k) / math.sqrt(q.shape[-1])
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhst,bthk->bshk", a, v)
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        y = layers.layernorm(x, p["ln2"]["scale"], p["ln2"]["bias"])
        return x + fc_apply(p["fc2"], jax.nn.gelu(fc_apply(p["fc1"], y)))

    for j in range(L):
        units.append((f"block_{j:02d}", blk_init, blk_apply))

    def head_init(key):
        return {"ln": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
                "fc": fc_init(key, d, cfg.vocab_size)}

    def head_apply(p, x):
        x = layers.layernorm(x, p["ln"]["scale"], p["ln"]["bias"])
        return fc_apply(p["fc"], jnp.mean(x, axis=1))

    units.append(("head", head_init, head_apply))
    return units


# ---------------------------------------------------------------------------
# model wrapper (streaming protocol)
# ---------------------------------------------------------------------------

class VisionModel:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        v = cfg.vision_variant
        if v in RESNET_BLOCKS:
            self._units = _resnet_units(cfg)
        elif v in VGG_STAGES:
            self._units = _vgg_units(cfg)
        elif v in VIT:
            self._units = _vit_units(cfg)
        else:
            raise ValueError(v)
        self._by_name = {n: (i, a) for n, i, a in self._units}
        self._abstract_units = {}

    def unit_names(self) -> List[str]:
        return [n for n, _, _ in self._units]

    def init_unit(self, name: str, key: jax.Array) -> PyTree:
        return self._by_name[name][0](key)

    def abstract_unit(self, name: str) -> PyTree:
        if name not in self._abstract_units:          # static per spec
            self._abstract_units[name] = jax.eval_shape(
                lambda: self.init_unit(name, jax.random.key(0)))
        return self._abstract_units[name]

    def stack_index(self, name: str) -> None:
        """Every unit is kept whole: nothing is scan-stacked."""
        return None

    def assemble(self, units: Dict[str, PyTree], stacked=None) -> PyTree:
        return dict(units)

    def init(self, key: jax.Array) -> PyTree:
        names = self.unit_names()
        ks = jax.random.split(key, len(names))
        return {n: self.init_unit(n, k) for n, k in zip(names, ks)}

    def abstract(self) -> PyTree:
        return jax.eval_shape(lambda: self.init(jax.random.key(0)))

    def unit_apply(self, name: str, uparams: PyTree,
                   state: Dict[str, Any]) -> Dict[str, Any]:
        apply = self._by_name[name][1]
        out = dict(state)
        if name == self._units[0][0]:
            x = state["batch"]["image"]
            x = jnp.transpose(x, (0, 2, 3, 1))        # NCHW -> NHWC
            out["x"] = apply(uparams, x)
        else:
            out["x"] = apply(uparams, state["x"])
        if name == self._units[-1][0]:
            out["logits"] = out["x"]
        return out

    def forward(self, params: PyTree, batch: Dict[str, jax.Array]):
        state: Dict[str, Any] = {"batch": batch}
        for name in self.unit_names():
            state = self.unit_apply(name, params[name], state)
        return state["logits"], jnp.zeros((), jnp.float32)

    def input_specs(self, kind: str, seq: int, batch: int):
        r = self.cfg.img_res
        return {"image": jax.ShapeDtypeStruct((batch, 3, r, r),
                                              jnp.float32)}


@functools.lru_cache(maxsize=None)
def build(cfg: ArchConfig) -> VisionModel:
    return VisionModel(cfg)
