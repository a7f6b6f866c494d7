"""Smoke run of the serving stack on TPU at the published widths of
qwen3-moe-235b-a22b, cut to one chip (``qwen3-moe-235b-a22b-1chip``).

    python chip_smoke.py               # one chip: phases A, B, C
    python chip_smoke.py --four-chips  # four chips: expert-parallel decode only

Phase A compiles each Pallas kernel of the served path at the preset's
widths and checks its output against the kernel's ``ref.py`` oracle, computed
in float32 at the highest matmul precision.  Phases B and C serve a queue of
16 requests (prompts of 512 to 2048 tokens, 32 new tokens each) on 8 slots
through ``repro.launch.serve``'s in-process fabric, B on the decode plane
with contiguous KV, C paged with a ``2,1`` draft tree and int8 KV and expert
stacks; every request must be answered and serve's own exit guards must
hold.  ``--four-chips`` runs only the sharded decode path: all 128 published
experts over a (data=1, model=4) mesh, decode logits against the same
model's non-cached full forward on the same mesh.

Every line before the last is informational (device kind, compile seconds,
tokens served, wall time, peak device memory), not a metric.  The last line
is the JSON verdict.  The script exits nonzero without printing it when JAX
finds no TPU, when it is not run from a checkout of this repository, or when
any phase fails.  Weights and inputs are random, made from fixed seeds.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
PRESET = "qwen3-moe-235b-a22b-1chip"
FOUR_CHIP_PRESET = "qwen3-moe-235b-a22b-4chip"
SLOTS, REQUESTS, PROMPT_LEN, GEN = 8, 16, 2048, 32

# Phase A tolerances, as max |kernel - oracle| / max |oracle|.  The oracle is
# f32 at the highest precision on bf16-valued inputs; the kernels accumulate
# in f32 but feed the MXU bf16 operands at default precision (the softmax
# weights and the SwiGLU hidden are rounded to bf16, 2^-9 relative), and the
# attention outputs are stored in bf16 (2^-9 relative).  A few such roundings
# summed over hd=128 or f=1536 terms stay well below 1%; 2% leaves margin
# without hiding a wrong mask or a wrong expert, which move the output by
# O(1).
ATTN_TOL = 2e-2
MOE_TOL = 2e-2
# Four-chip tolerance on logits, as max |decode - forward| / max |forward|:
# the two paths run different kernels (flash-decode and moe_decode against
# blockwise attention and grouped GEMMs) with bf16 activations through 4
# layers, so they agree to bf16 rounding compounded per layer (about 5e-3),
# not bitwise.  Faults planted in the decode plane's shard_map body, run at
# d=256 on four CPU devices, read 7.2e-2 (one chip's partial zeroed before
# the psum), 1.3e-1 (psum removed) and 2.3e-1 (expert offset off by one);
# 2e-2 sits between, and the run's own negative control must exceed it.
LOGIT_TOL = 2e-2


class PhaseFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def compile_seconds_counter():
    """Sum of backend compile durations JAX reports from now on."""
    from jax import monitoring

    total = [0.0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration

    monitoring.register_event_duration_secs_listener(listen)
    return total


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} bytes ({peak / 2**30:.2f} GiB)"


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))), 1e-30))


# ---------------------------------------------------------------------------
# phase A: each kernel at the preset's widths against its oracle
# ---------------------------------------------------------------------------


def kernel_cases(cfg):
    """(name, kernel fn, oracle fn, args, tolerance) at the preset's widths."""
    import jax
    import jax.numpy as jnp

    from repro.core.plans import DecodePlan
    from repro.core.quant import quantize_int8
    from repro.kernels.flash_attention import decode as fd
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.flash_attention import ref as fref
    from repro.kernels.moe_decode import ops as md
    from repro.kernels.moe_decode import ref as mref

    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    d, f, E, k = cfg.d_model, cfg.d_ff_expert, cfg.num_experts, cfg.top_k
    B, S, W, ps = SLOTS, PROMPT_LEN + GEN, 1024, cfg.page_size
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    bf16 = jnp.bfloat16

    def normal(shape, dtype=bf16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def quant_rows(x):
        q, s = quantize_int8(x.astype(jnp.float32), axis=(-2, -1))
        return q, s[..., 0, 0]

    lengths = jax.random.randint(next(keys), (B,), 512, PROMPT_LEN + 1, jnp.int32)
    tree = dict(ancestors=jnp.asarray((1, 3, 5, 11), jnp.int32), base=lengths)
    kc, vc = normal((B, S, nkv, hd)), normal((B, S, nkv, hd))
    (kq, ks), (vq, vs) = quant_rows(kc), quant_rows(vc)
    scl = jnp.stack([ks, vs])
    # paged pool: every slot's logical pages scattered over a shuffled pool
    mp = -(-S // ps)
    perm = jax.random.permutation(next(keys), B * mp).astype(jnp.int32).reshape(B, mp)

    def pool(c):
        pad = mp * ps - S
        c = jnp.pad(c, [(0, 0), (0, pad)] + [(0, 0)] * (c.ndim - 2))
        rows = (perm[:, :, None] * ps + jnp.arange(ps)).reshape(B, mp * ps)
        return jnp.zeros((B * mp * ps,) + c.shape[2:], c.dtype).at[rows].set(c)

    pk, pv, pkq, pvq = pool(kc), pool(vc), pool(kq), pool(vq)
    pscl = jnp.stack([pool(ks), pool(vs)])

    cases = []
    for T, tr in ((1, {}), (4, tree)):
        shape = "tree4" if tr else "chain1"
        q = normal((B, T, nq, hd))
        for quant, (kk, vv, sc) in (("bf16", (kc, vc, None)), ("int8", (kq, vq, scl))):
            cases.append((
                f"flash_decode[{shape},{quant}]",
                lambda q, kk, vv, sc, tr=tr: fd.flash_decode(q, kk, vv, lengths, scales=sc, **tr),
                lambda q, kk, vv, sc, tr=tr: fref.flash_decode(q, kk, vv, lengths, scales=sc, **tr),
                (q, kk, vv, sc), ATTN_TOL,
            ))
        for quant, (kk, vv, sc) in (("bf16", (pk, pv, None)), ("int8", (pkq, pvq, pscl))):
            cases.append((
                f"flash_decode_paged[{shape},{quant}]",
                lambda q, kk, vv, sc, tr=tr: fd.flash_decode_paged(
                    q, kk, vv, lengths, perm, page_size=ps, scales=sc, **tr),
                lambda q, kk, vv, sc, tr=tr: fref.flash_decode_paged(
                    q, kk, vv, lengths, perm, page_size=ps, scales=sc, **tr),
                (q, kk, vv, sc), ATTN_TOL,
            ))
    q1 = normal((B, 1, nq, hd))
    pos = lengths + W  # past the first wrap of the W-slot rolling buffer
    for quant, (kk, vv, sc) in (("bf16", (kc[:, :W], vc[:, :W], None)),
                                ("int8", (kq[:, :W], vq[:, :W], scl[:, :, :W]))):
        cases.append((
            f"flash_decode_window[{quant}]",
            lambda q, kk, vv, sc: fd.flash_decode_window(q, kk, vv, pos, window=W // 2, scales=sc),
            lambda q, kk, vv, sc: fref.flash_decode_window(q, kk, vv, pos, window=W // 2, scales=sc),
            (q1, kk, vv, sc), ATTN_TOL,
        ))

    Tm = B * 4  # a tree-width launch: every slot's 4 draft nodes
    x = normal((Tm, d))
    ids = jax.random.randint(next(keys), (Tm, k), 0, E, jnp.int32)
    wts = jax.nn.softmax(jax.random.normal(next(keys), (Tm, k)), axis=-1)
    stacks = {"w_gate": normal((E, d, f)) / 64.0, "w_up": normal((E, d, f)) / 64.0,
              "w_down": normal((E, f, d)) / 40.0}
    from repro.models.moe import quantize_expert_stacks

    qstacks = quantize_expert_stacks(stacks)
    for quant, p in (("bf16", stacks), ("int8", qstacks)):
        names = ("w_gate", "w_up", "w_down") if quant == "bf16" else ("w_gate_q", "w_up_q", "w_down_q")
        scales = None if quant == "bf16" else jnp.stack([p["w_gate_s"], p["w_up_s"], p["w_down_s"]])
        cases.append((
            f"moe_decode[{quant}]",
            lambda x, p: md.decode_moe(x, DecodePlan(ids, wts), p),
            lambda x, p, names=names, scales=scales: mref.decode_moe(
                x, ids, wts, *(p[n] for n in names), scales=scales),
            (x, p), MOE_TOL,
        ))

    qp, kp, vp = normal((1, PROMPT_LEN, nq, hd)), normal((1, PROMPT_LEN, nkv, hd)), normal((1, PROMPT_LEN, nkv, hd))
    cases.append((
        "flash_attention[prefill]",
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        lambda q, k, v: fref.flash_attention(q, k, v, causal=True),
        (qp, kp, vp), ATTN_TOL,
    ))
    return cases


def phase_a(cfg) -> None:
    import jax
    import jax.numpy as jnp

    def f32(tree):
        return jax.tree.map(
            lambda a: a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    failed = []
    for name, kern, oracle, args, tol in kernel_cases(cfg):
        t0 = time.perf_counter()
        compiled = jax.jit(kern).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        got = jax.block_until_ready(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*f32(args))
        err = rel_err(got, want)
        ok = err <= tol
        log(f"A {name}: compile {compile_s:.2f} s, max rel err {err:.3e} (tol {tol:g})"
            f"{'' if ok else '  FAILED'}")
        if not ok:
            failed.append(name)
    if failed:
        raise PhaseFailure(f"phase A: {failed} disagree with their oracles")


# ---------------------------------------------------------------------------
# phases B and C: the serve loop through its normal entry point
# ---------------------------------------------------------------------------


def phase_serve(tag: str, extra, device, compile_total) -> None:
    from repro.launch import serve

    argv = ["--arch", PRESET, "--decode-plane", "--slots", str(SLOTS),
            "--requests", str(REQUESTS), "--prompt-len", str(PROMPT_LEN),
            "--gen", str(GEN), *extra]
    log(f"{tag} serve {' '.join(argv)}")
    c0 = compile_total[0]
    report = serve.main(argv)
    log(f"{tag} compile {compile_total[0] - c0:.1f} s; served {report.get('served')}"
        f"/{REQUESTS} requests, {report.get('tokens')} tokens in "
        f"{report.get('wall_s', float('nan')):.1f} s wall (compile included); "
        f"peak device memory {peak_bytes(device)}")
    if report["code"] != 0:
        raise PhaseFailure(f"phase {tag}: serve's exit guards failed")
    if report["served"] != REQUESTS or report["tokens"] < REQUESTS * GEN:
        raise PhaseFailure(f"phase {tag}: {report['served']} of {REQUESTS} requests "
                           f"answered, {report['tokens']} tokens")


# ---------------------------------------------------------------------------
# four chips: expert-parallel decode against the full forward
# ---------------------------------------------------------------------------


def four_chip_phase(base, compile_total) -> None:
    """Decode logits of the 128-expert preset over a (data=1, model=4) mesh
    against the same model's non-cached full forward on that mesh.

    The decode plane executes plans routed one token earlier than the full
    forward does, so random routing would make the two models differ by
    design.  The routing is therefore pinned to a constant feature: residual
    dim 0 is held constant (that embedding column is constant, and no
    projection writes that dim), and each router reads only dim 0.  Every
    token of layer l then goes to the same 8 experts, chosen two per chip,
    with non-uniform weights, in both paths, so each chip's
    ``DecodePlan.shard_slice`` keeps its two and the per-layer psum combines
    four partial outputs.  Capacity is set so prefill drops nothing, since
    decode never drops.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_model, init_sharded_params
    from repro.models import transformer as trf
    from repro.parallel.sharding import cache_shardings

    cfg = dataclasses.replace(
        base, decode_plane=True, capacity_factor=base.num_experts / base.top_k,
    )
    mesh = make_host_mesh(1, 4)
    B, S, G = 2, 256, 8
    E, k, L = cfg.num_experts, cfg.top_k, cfg.num_layers
    ep = E // 4
    # layer l routes to experts {c * 32 + (5l + 3c) % 32, c * 32 + (7l + 11 + c) % 32}
    chosen = np.array([[c * ep + (5 * l + 3 * c) % ep for c in range(4)]
                       + [c * ep + (7 * l + 11 + c) % ep for c in range(4)] for l in range(L)])
    logit_rows = np.full((L, E), -4.0, np.float32)
    for l in range(L):
        logit_rows[l, chosen[l]] = np.linspace(2.0, 0.5, k)

    t0 = time.perf_counter()
    params = init_sharded_params(cfg, mesh, jax.random.PRNGKey(0))
    shard = jax.tree.map(lambda a: a.sharding, params)
    const = 0.5  # residual dim 0 = const * sqrt(d) in every layer

    def pin_routing(p):
        p = dict(p)
        p["embed"] = p["embed"].at[:, 0].set(const)
        blocks = p["blocks"]["scan"]["b0"]
        attn = dict(blocks["attn"], wo=blocks["attn"]["wo"].at[..., 0].set(0))
        mo = dict(blocks["moe"], w_down=blocks["moe"]["w_down"].at[..., 0].set(0))
        scale = const * np.sqrt(cfg.d_model)
        router = jnp.zeros_like(mo["router"]).at[:, 0, :].set(jnp.asarray(logit_rows / scale))
        mo["router"] = router
        p["blocks"] = {"scan": {"b0": dict(blocks, attn=attn, moe=mo)}, "rest": p["blocks"]["rest"]}
        return p

    params = jax.jit(pin_routing, out_shardings=shard, donate_argnums=0)(params)
    model = build_model(cfg, mesh, B)
    max_len = S + G
    c_abs = jax.eval_shape(lambda: trf.init_cache(cfg, B, max_len))
    cache = model.init_cache(B, max_len, shardings=cache_shardings(c_abs, B, mesh))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size, jnp.int32)

    prefill = jax.jit(model.prefill)
    step = jax.jit(model.decode_step)
    logits, cache = prefill(params, prompts, cache)
    got, toks = [logits], [jnp.argmax(logits, -1).astype(jnp.int32)]
    for i in range(G - 1):
        logits, cache = step(params, cache, toks[-1], jnp.int32(S + i))
        got.append(logits)
        toks.append(jnp.argmax(logits, -1).astype(jnp.int32))
    seq = jnp.concatenate([prompts, jnp.stack(toks[:-1], axis=1)], axis=1)  # (B, S + G - 1)
    forward = jax.jit(model.forward)
    full, _ = forward(params, seq)
    want = full[:, S - 1:]  # logits that predict tokens S .. S + G - 1
    got = jnp.stack(got, axis=1)
    jax.block_until_ready((got, want))
    wall = time.perf_counter() - t0

    # negative control: the forward with chip 3's experts silenced is what a
    # decode step that lost that chip's partial sum would produce (chip 3
    # carries the smallest share, 17.6%, of the pinned routing weight, so
    # this is the mildest such fault); the tolerance must reject it
    def drop_chip3(p):
        b0 = p["blocks"]["scan"]["b0"]
        mo = dict(b0["moe"], w_down=b0["moe"]["w_down"].at[:, 3 * ep:].set(0))
        return dict(p, blocks={"scan": {"b0": dict(b0, moe=mo)}, "rest": p["blocks"]["rest"]})

    dropped, _ = forward(jax.jit(drop_chip3, out_shardings=shard)(params), seq)
    fault_err = rel_err(dropped[:, S - 1:], want)

    psums = step.lower(params, cache, toks[-1], jnp.int32(S)).as_text().count("all_reduce")
    err = rel_err(got, want)
    got_top = np.asarray(jnp.argmax(got, -1))
    want_np = np.asarray(want, np.float32)
    flips = got_top != want_np.argmax(-1)
    top2 = np.sort(want_np, -1)[..., -2:]
    gap = (top2[..., 1] - top2[..., 0]) / np.max(np.abs(want_np))
    devices = jax.devices()
    log(f"4chip mesh data=1 model=4 over {len(devices)} x {devices[0].device_kind}; "
        f"{E} experts, {ep} per chip; compile {compile_total[0]:.1f} s, wall {wall:.1f} s")
    log(f"4chip decode vs full forward over {G} positions x {B} sequences: max rel err "
        f"{err:.3e} (tol {LOGIT_TOL:g}); negative control, chip 3's experts dropped: "
        f"{fault_err:.3e}; psum sites in the lowered decode step: {psums} (one "
        f"layer-scan body, run for each of {L} MoE layers)")
    log(f"4chip argmax agreement {1 - flips.mean():.3f}; forward's top-2 logit gap / "
        f"max |logit| at the {int(flips.sum())} flipped positions: "
        f"{[float(f'{g:.3e}') for g in gap[flips]]}, median over all positions "
        f"{float(np.median(gap)):.3e}")
    for dev in devices:
        log(f"4chip {dev}: peak device memory {peak_bytes(dev)}")
    if not err <= LOGIT_TOL:
        raise PhaseFailure("four-chip decode logits disagree with the full forward")
    if not fault_err > LOGIT_TOL:
        raise PhaseFailure("four-chip tolerance does not reject a dropped chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the expert-parallel decode path on four chips")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX finds {len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import use_compile_cache
    from repro.configs import get_config

    log(f"device_kind {devices[0].device_kind}, {len(devices)} device(s), "
        f"compile cache {use_compile_cache()}")
    compile_total = compile_seconds_counter()
    try:
        if args.four_chips:
            four_chip_phase(get_config(FOUR_CHIP_PRESET), compile_total)
        else:
            phase_a(get_config(PRESET))
            phase_serve("B", [], devices[0], compile_total)
            phase_serve("C", ["--paged", "--draft-tree", "2,1", "--kv-dtype", "int8",
                              "--expert-dtype", "int8"], devices[0], compile_total)
    except PhaseFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
