"""Shared benchmark harness: deploy models, run strategies, CSV output.

Default model set is the paper's own trio (one per family:
ResNet-50 / VGG-16 / ViT-B-16) at full size; ``--sweep`` runs all ten
paper models; ``--quick`` uses smoke variants (CI).  The simulated
storage device (800 MB/s, 0.2 ms latency — cloud local-NVMe envelope)
makes the I/O phase visible where this container's page cache would
hide it (documented deviation; the byte copies still happen).
"""
from __future__ import annotations

import argparse
import os
import tempfile
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import ColdStartEngine, LoadResult
from repro.models import transformer
from repro.models.api import get_config
from repro.store.store import BandwidthModel, WeightStore, deploy_model

PAPER_TRIO = ["resnet50", "vgg16", "vit_b_16"]
PAPER_ALL = ["resnet50", "resnet101", "resnet152",
             "vgg11", "vgg13", "vgg16", "vgg19",
             "vit_b_16", "vit_b_32", "vit_l_16"]
STRATEGIES = ["traditional", "pisel", "mini", "preload", "cicada"]

_STORE_CACHE: Dict[Tuple[str, bool], str] = {}


def std_parser(**defaults) -> argparse.ArgumentParser:
    """The benchmarks' shared CLI; every benchmark entry point builds
    its parser here, before any compile, so this is also where the
    persistent compilation cache is turned on."""
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", nargs="+",
                    default=defaults.get("models", PAPER_TRIO))
    ap.add_argument("--sweep", action="store_true",
                    help="all 10 paper models")
    ap.add_argument("--quick", action="store_true",
                    help="smoke-size models (CI)")
    ap.add_argument("--strategies", nargs="+",
                    default=defaults.get("strategies", STRATEGIES))
    ap.add_argument("--bandwidth-mbps", type=float, default=400.0)
    ap.add_argument("--repeats", type=int,
                    default=defaults.get("repeats", 1))
    ap.add_argument("--store-dir", default=None)
    return ap


def model_list(args) -> List[str]:
    return PAPER_ALL if args.sweep else args.models


def make_batch(cfg):
    r = np.random.default_rng(0)
    if cfg.family.value == "vision":
        return {"image": jnp.asarray(
            r.standard_normal((1, 3, cfg.img_res, cfg.img_res)),
            jnp.float32)}
    return {"tokens": jnp.asarray(
        r.integers(0, cfg.vocab_size, (1, 32)), jnp.int32)}


def deployed_store(args) -> Tuple[WeightStore, str]:
    """Persistent across benchmark modules in one process run."""
    key = (args.store_dir or "default", args.quick)
    if key not in _STORE_CACHE:
        _STORE_CACHE[key] = args.store_dir or tempfile.mkdtemp(
            prefix="cicada-bench-")
    d = _STORE_CACHE[key]
    store = WeightStore(d, BandwidthModel(args.bandwidth_mbps, 0.2))
    return store, d


def get_model(name: str, quick: bool):
    cfg = get_config(name, smoke=quick)
    return cfg, transformer.build(cfg)


def ensure_deployed(store: WeightStore, name: str, quick: bool):
    cfg, model = get_model(name, quick)
    if not store.has_model(name):
        deploy_model(store, model, name, jax.random.key(0))
    return cfg, model


_ENGINE_CACHE: Dict[Tuple[str, str, bool], ColdStartEngine] = {}


def load_with_strategy(store: WeightStore, name: str, strategy: str,
                       quick: bool) -> LoadResult:
    cfg, model = ensure_deployed(store, name, quick)
    batch = make_batch(cfg)
    ck = (name, strategy, quick)
    if ck not in _ENGINE_CACHE:
        eng = ColdStartEngine(model, name, store, strategy=strategy)
        eng.warmup(batch)
        _ENGINE_CACHE[ck] = eng
    return _ENGINE_CACHE[ck].load(batch)


def print_csv(header: List[str], rows: List[List]):
    print(",".join(header))
    for r in rows:
        print(",".join(f"{v:.6g}" if isinstance(v, float) else str(v)
                       for v in r))
