"""A single-device cold start builds the steady-state params in place.

Each applied block unit is written into its index of the scan-stacked
params by a donated update, so a load holds its layers once (plus the
units E has not yet run), never a second stacked copy; and an evicted
instance lets go of its weights.
"""
import gc
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ColdStartEngine
from repro.core import units as units_mod
from repro.core.coldstart import Placement
from repro.models import transformer
from repro.models.api import ArchConfig, Family, get_config
from repro.serving.pool import FunctionInstance
from repro.store.store import WeightStore, deploy_model, unflatten_unit

# layers large beside the activations, so a second copy of them shows
WIDE = ArchConfig(name="inplace-test", family=Family.DENSE, n_layers=6,
                  d_model=128, n_heads=4, n_kv_heads=2, d_ff=512,
                  vocab_size=256)
S = 8


def _nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def _assert_bit_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def deployed(tmp_path_factory):
    m = transformer.build(WIDE)
    store = WeightStore(str(tmp_path_factory.mktemp("store")))
    deploy_model(store, m, "m", jax.random.key(5))
    units = {}
    for u in m.unit_names():
        leaves = store.read_and_deserialize("m", u)
        units[u] = unflatten_unit(m.abstract_unit(u),
                                  {k: v for k, (v, _) in leaves.items()})
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(1).integers(0, WIDE.vocab_size, (1, S)),
        jnp.int32)}
    eng = ColdStartEngine(m, "m", store, strategy="cicada")
    st = {"batch": batch}                 # E over the stored units
    for u in m.unit_names():
        st = eng._apply_fn(u)(units[u], st)
    return m, store, units, batch, np.asarray(st["logits"])


@pytest.mark.parametrize("arch", ["inplace-test", "recurrentgemma-2b"])
@pytest.mark.parametrize("order", ["in_order", "reversed", "shuffled"])
def test_placement_is_the_stack(arch, order):
    """Placed in any order, the units give exactly ``assemble``'s
    stacked params: dense (one slot) and hybrid (three slots and a
    tail unit)."""
    m = transformer.build(WIDE if arch == "inplace-test"
                          else get_config(arch, smoke=True))
    names = m.unit_names()
    keys = jax.random.split(jax.random.key(3), len(names))
    units = {n: m.init_unit(n, k) for n, k in zip(names, keys)}
    seq = {"in_order": names, "reversed": names[::-1],
           "shuffled": [names[i] for i in
                        np.random.default_rng(0).permutation(len(names))]}
    p = Placement(m)
    for u in seq[order]:
        p.place(u, units[u])
    _assert_bit_equal(p.params(), m.assemble(units))
    for u in names:                      # and each unit reads back as put
        _assert_bit_equal(p.view(u), units[u])


def test_each_write_donates_the_stacked_buffers():
    m = transformer.build(WIDE)
    names = m.unit_names()
    p = Placement(m)
    p.place(names[1], m.init_unit(names[1], jax.random.key(0)))
    before = jax.tree.leaves(p.stacked["s0"])
    p.place(names[2], m.init_unit(names[2], jax.random.key(1)))
    assert all(x.is_deleted() for x in before)


def _reversed_streams(monkeypatch):
    """One I/O worker fetching the units last first, so the weight unit
    applies them out of order."""
    from repro.core.decoupler import WeightDecoupler
    orig = WeightDecoupler.prefetch
    monkeypatch.setattr(WeightDecoupler, "prefetch",
                        lambda self, us: orig(self, list(us)[::-1]))


@pytest.mark.parametrize("strategy,shuffle", [
    ("traditional", False), ("pisel", False), ("cicada", False),
    ("cicada", True)], ids=["traditional", "pisel", "cicada",
                            "cicada-out-of-order"])
def test_load_params_bit_equal_to_the_stack(deployed, monkeypatch,
                                            strategy, shuffle):
    """Both single-device load paths (Fig. 1's and the pipeline) give
    params bit-equal to stacking the stored units, and the E units'
    logits bit-equal to E over the stored units."""
    m, store, units, batch, logits = deployed
    if shuffle:
        _reversed_streams(monkeypatch)
    eng = ColdStartEngine(m, "m", store, strategy=strategy,
                          io_workers=1 if shuffle else 4)
    res = eng.load(batch)
    _assert_bit_equal(res.params, m.assemble(units))
    np.testing.assert_array_equal(np.asarray(res.logits), logits)
    applied = [e.layer for e in sorted(res.trace.events_for("A").values(),
                                       key=lambda e: e.t_end)]
    if shuffle:                          # forced out of order
        assert applied != m.unit_names()
    elif strategy != "cicada":           # Fig. 1 and PISeL: in order
        assert applied == m.unit_names()


@pytest.mark.parametrize("strategy,shuffle", [
    ("traditional", False), ("cicada", False), ("cicada", True)],
    ids=["traditional", "cicada", "cicada-out-of-order"])
def test_live_bytes_during_a_load(deployed, monkeypatch, strategy, shuffle):
    """Sampled after every A, placement, E and the final assemble, the
    device bytes the load added never exceed the model's (stacked layers
    + embed + final) + the units in flight (applied, and not yet both
    run by E and written into the stacked params) + the activations.  The traditional strategy (Fig. 1) runs E after every
    A, from a copy of each placed unit in turn, and holds its full
    numerical initialization (its construction) throughout.  Stacking
    after the load, as before, held the layers twice."""
    m, store, units, batch, _ = deployed
    if shuffle:
        _reversed_streams(monkeypatch)
    size = {u: _nbytes(t) for u, t in units.items()}
    model = sum(size.values())
    acts = 4 * S * (WIDE.vocab_size + 2 * WIDE.d_model) * 4
    slack = acts + (max(size.values()) + model
                    if strategy == "traditional" else 0)
    eng = ColdStartEngine(m, "m", store, strategy=strategy,
                          io_workers=1 if shuffle else 4)
    eng.warmup(batch)
    lock = threading.Lock()
    # in flight: from the start of its A until both E has run it and A
    # has let go of it (A holds a unit until it has written it and
    # begun the next)
    unrun, unwritten, written = set(), set(), set()
    worst = []

    def sample(what):
        with lock:
            gc.collect()
            live = sum(a.nbytes for a in jax.live_arrays()) - base
            bound = model + sum(size[u] for u in unrun | unwritten) \
                + slack
            worst.append((live - bound, what, live, bound))

    apply_unit, drop = eng._apply_unit, units_mod.PipelineState.drop
    place, params = Placement.place, Placement.params

    def _apply(u, *a, **kw):
        with lock:
            unwritten.difference_update(written)
            if strategy != "traditional":
                unrun.add(u)
                unwritten.add(u)
        out = apply_unit(u, *a, **kw)
        sample(f"A {u}")
        return out

    def _drop(self, stage, u):
        drop(self, stage, u)
        with lock:
            unrun.discard(u)
        sample(f"E {u}")

    def _place(self, u, tree):
        place(self, u, tree)
        sample(f"place {u}")
        with lock:
            written.add(u)

    def _params(self):
        with lock:
            unwritten.difference_update(written)
        out = params(self)
        sample("assemble")
        return out

    monkeypatch.setattr(eng, "_apply_unit", _apply)
    monkeypatch.setattr(units_mod.PipelineState, "drop", _drop)
    monkeypatch.setattr(Placement, "place", _place)
    monkeypatch.setattr(Placement, "params", _params)
    gc.collect()
    base = sum(a.nbytes for a in jax.live_arrays())
    res = eng.load(batch)
    n_blocks = sum(u.startswith("block_") for u in m.unit_names())
    assert len(worst) >= 2 * n_blocks
    over = max(worst)
    assert over[0] <= 0, over
    assert _nbytes(res.params) == model


def test_evicted_instance_releases_its_weights(deployed):
    m, store, units, batch, _ = deployed
    inst = FunctionInstance(m, "m", store, example_batch=batch, warm=False)
    assert inst.ensure_live()
    refs = [weakref.ref(x) for x in jax.tree.leaves(inst.params)]
    construct_s = inst.last_load.trace.work_by_stage()["L"]
    inst.evict()
    gc.collect()
    assert not inst.live
    assert all(r() is None for r in refs)
    # what the benchmark and the stats read of the load is kept
    assert inst.last_load.trace.work_by_stage()["L"] == construct_s
