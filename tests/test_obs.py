"""The program's span recorder and counters (``repro.obs``), the serve
replica's spans, and the named scopes of the decode launch, on the CPU."""
from __future__ import annotations

import dataclasses
import gc
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs


def _named(snap, name):
    return [s for s in snap["spans"] if s.name == name]


def test_nesting_sets_parent_ids_and_stamps():
    ring = obs.Ring(16)
    with obs.span("outer", ring=ring, k=1) as outer:
        with obs.span("inner", ring=ring) as inner:
            pass
        with obs.span("inner2", ring=ring):
            pass
    with obs.span("alone", ring=ring):
        pass
    snap = ring.snapshot()
    by = {s.name: s for s in snap["spans"]}
    assert [s.name for s in snap["spans"]] == ["inner", "inner2", "outer", "alone"]
    assert by["inner"].parent == by["inner2"].parent == by["outer"].id
    assert by["outer"].parent == 0 and by["alone"].parent == 0
    assert by["outer"].attrs == {"k": 1}
    assert outer.start <= inner.start <= inner.end <= outer.end
    assert (by["outer"].start, by["outer"].end) == (outer.start, outer.end)
    assert snap["dropped"] == 0


def test_ring_keeps_the_newest_and_counts_what_it_dropped():
    ring = obs.Ring(4)
    for i in range(10):
        with obs.span("s", ring=ring, i=i):
            pass
    obs.count("hits", ring=ring)
    obs.count("hits", 2, ring=ring)
    snap = ring.snapshot()
    assert [s.attrs["i"] for s in snap["spans"]] == [6, 7, 8, 9]
    assert snap["dropped"] == 6
    assert snap["counters"] == {"hits": 3}


def test_span_closes_on_an_exception():
    ring = obs.Ring(4)
    try:
        with obs.span("fails", ring=ring):
            raise ValueError("x")
    except ValueError:
        pass
    with obs.span("next", ring=ring):
        pass
    snap = ring.snapshot()
    assert [s.name for s in snap["spans"]] == ["fails", "next"]
    assert snap["spans"][1].parent == 0


def test_forced_full_collection_gives_a_gc_span():
    obs.install()
    obs.install()  # idempotent: one hook
    t0 = time.perf_counter()
    before = obs.snapshot()["counters"].get("gc.collections.2", 0)
    with obs.span("around"):
        gc.collect(2)
    snap = obs.snapshot()
    around = _named(snap, "around")[-1]
    mine = [s for s in _named(snap, "gc") if s.start >= t0 and s.parent == around.id]
    assert len(mine) == 1
    assert mine[0].attrs["gen"] == 2 and mine[0].attrs["collected"] >= 0
    assert around.start <= mine[0].start <= mine[0].end <= around.end
    assert snap["counters"]["gc.collections.2"] >= before + 1


def test_fresh_jit_gives_compile_spans_with_its_name():
    obs.install()
    t0 = time.perf_counter()

    def obs_probe_fn(x):
        return x * 3 + 1

    jax.jit(obs_probe_fn)(jnp.arange(5.0)).block_until_ready()
    t1 = time.perf_counter()
    snap = obs.snapshot()
    mine = [s for s in _named(snap, "compile") if "obs_probe_fn" in s.attrs["fun_name"]]
    events = {s.attrs["event"] for s in mine}
    assert "backend_compile_duration" in events and "jaxpr_trace_duration" in events
    assert all(t0 - 0.05 <= s.start <= s.end <= t1 + 0.05 for s in mine)
    assert snap["counters"]["compiles.jit(obs_probe_fn)"] == 1


def test_spans_lie_on_a_host_plane_of_the_profile(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("serve.step", step=1):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    names = [(p.name, e.name) for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines for e in line.events]
    assert any(e == "serve.step" for _, e in names), names[:20]


def _tiny_replica(**kw):
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import ServeReplica
    from repro.models.model import Model

    cfg = dataclasses.replace(get_smoke_config("qwen3-32b"), num_layers=2, **kw)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    return ServeReplica(cfg, make_host_mesh(1, 1), 2, 24, params)


def test_admit_and_step_give_the_span_tree_in_order():
    from repro.runtime.fabric import Request

    rep = _tiny_replica()
    rep.admit(Request(rid=41, prompt=np.arange(6, dtype=np.int32), gen=3))  # compiles
    rep.step()
    t0 = time.perf_counter()
    rep.admit(Request(rid=42, prompt=np.arange(6, dtype=np.int32) + 1, gen=3))
    rep.step()
    spans = [s for s in obs.snapshot()["spans"] if s.start >= t0 and s.name.startswith("serve.")]
    by_id = {s.id: s for s in spans}
    admit = [s for s in spans if s.name == "serve.admit"]
    step = [s for s in spans if s.name == "serve.step"]
    assert len(admit) == 1 and len(step) == 1
    assert admit[0].attrs == {"rid": 42, "prompt_len": 6, "slots": 1}
    assert step[0].attrs == {"step": rep.steps, "active": 2}
    kids = lambda p: [s.name for s in sorted(spans, key=lambda s: s.start) if s.parent == p.id]
    assert kids(admit[0]) == ["serve.admit.prefill", "serve.admit.write", "serve.admit.sync",
                              "serve.admit.host"]
    assert kids(step[0]) == ["serve.step.prepare", "serve.step.dispatch", "serve.step.sync",
                             "serve.step.verify", "serve.step.retire"]
    assert admit[0].end <= step[0].start
    for s in spans:
        if s.parent in by_id:
            p = by_id[s.parent]
            assert p.start <= s.start <= s.end <= p.end


def test_prefill_ms_runs_to_the_host_read_of_the_logits():
    from repro.runtime.fabric import Request

    rep = _tiny_replica()
    t0 = time.perf_counter()
    rep.admit(Request(rid=7, prompt=np.arange(5, dtype=np.int32), gen=2))
    snap = obs.snapshot()
    admit = [s for s in _named(snap, "serve.admit") if s.start >= t0][-1]
    sync = [s for s in _named(snap, "serve.admit.sync") if s.parent == admit.id][-1]
    assert rep.prefill_ms == (sync.end - admit.start) * 1e3
    assert rep.prefills == 1


def test_decode_step_carries_the_named_scopes():
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeCell
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_spec_serve_step

    cfg = dataclasses.replace(get_smoke_config("qwen3-32b"), num_layers=2, use_pallas=True)
    mesh = make_host_mesh(1, 1)
    with mesh:
        text = build_spec_serve_step(cfg, mesh, ShapeCell("d", 32, 2, "decode")).lower().as_text(
            debug_info=True)
    # op locations name their scope path, nested ones relative to the outer
    for scope in ("embed", "layers", "attn.qkv", "attn.kv_write", "attn.kv_read",
                  "attn.kernel", "attn.out", "ffn", "head"):
        assert re.search(rf'loc\("([^"]*/)?{re.escape(scope)}/', text), scope
    assert "spec_step" in text


# (preset, overrides) -> (decode.kv_in_place, decode.kv_sliced) counted in
# one trace: scanned layers only, the unstacked "rest" layers in neither
KV_PATHS = {
    "dense": (("qwen3-32b", {}), (2, 0)),
    "attn_local_rest": (("qwen3-32b", dict(block_pattern=("attn", "local"), num_layers=5,
                                           local_window=8)), (2, 2)),
    "paged": (("qwen3-32b", dict(block_pattern=("attn", "local"), num_layers=4,
                                 local_window=8, paged=True, page_size=8)), (0, 4)),
    "recurrent": (("recurrentgemma-2b", dict(num_layers=6)), (0, 6)),
}


@pytest.mark.parametrize("case", list(KV_PATHS))
def test_decode_trace_counts_the_kv_path_of_each_scanned_layer(case):
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.models.model import Model

    (preset, over), want = KV_PATHS[case]
    cfg = dataclasses.replace(get_smoke_config(preset), **over)
    model = Model(cfg)
    B, max_len = 2, 16
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(B, max_len))
    kw = {"pages": T.identity_page_table(cfg, B, max_len)} if cfg.paged else {}
    names = ("decode.kv_in_place", "decode.kv_sliced")
    before = [obs.snapshot()["counters"].get(n, 0) for n in names]
    jax.eval_shape(lambda p, c: model.decode_tokens(
        p, c, jnp.zeros((B, 1), jnp.int32), jnp.zeros((B,), jnp.int32), **kw), params, cache)
    after = [obs.snapshot()["counters"].get(n, 0) for n in names]
    assert tuple(a - b for a, b in zip(after, before)) == want
