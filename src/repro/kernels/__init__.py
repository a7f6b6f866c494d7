"""Pallas TPU kernels for the perf-critical data-plane hot spots.

Each kernel package has: kernel.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), ops.py (jit'd wrapper; interpret=True on CPU), ref.py (pure-jnp
oracle used by the allclose test sweeps).

  moe_dispatch     plan-driven token permute/combine (control-plane consumer;
                   the CS-Benes permutation+broadcast analogue)
  moe_fused        fused MoE data plane: plan-steered gather -> grouped GEMM
                   -> weighted scatter in two launches (no (E, C, d) HBM
                   round-trips); opt-in (fused=True) and interpret-only so
                   far — the TPU compiler refuses its per-row gather
  moe_decode       tiny-T decode MoE: DecodePlan-steered expert SwiGLU in ONE
                   launch — the plan's expert ids drive the weight-tile DMA;
                   no sort, no capacity, no slot tensors (Agile decode plane)
  grouped_gemm     per-expert GEMM over dispatched slots (MXU-tiled); the
                   prefill MoE data plane whenever kernels run
  flash_attention  blocked causal/local attention forward (online softmax);
                   decode.py adds the length-steered one-token variant that
                   reads only the valid cache prefix
  rglru_scan       RG-LRU blocked linear recurrence (RecurrentGemma)
  ssd_scan         Mamba-2 chunked state-space-dual scan
"""


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"



def use_kernels(cfg) -> bool:
    """Whether the model runs its Pallas kernels.  On TPU always — the served
    path never falls back to the jnp twins there.  Off TPU only when
    ``cfg.use_pallas`` asks for them (interpret mode: CPU parity tests)."""
    return on_tpu() or bool(cfg.use_pallas)


def resolve_interpret(interpret=None) -> bool:
    """Whether a Pallas launch runs in the interpreter.

    On TPU never: the served path compiles every kernel, and a kernel that
    the compiler refuses fails the run.  Elsewhere the interpreter is the
    default (CPU tests); ``interpret=False`` off-TPU lowers the kernel for
    the TPU compiler, which is how a compile for a described (unattached)
    chip is rehearsed."""
    if on_tpu():
        return False
    return True if interpret is None else bool(interpret)


def shard_over_heads(fn, args, head_dims, out_head_dim: int = 2):
    """Call ``fn(*args)`` once per device when traced under a multi-device mesh.

    XLA cannot partition a Mosaic kernel, so under a mesh the launch runs in
    ``shard_map``: argument ``i`` is split over the mesh's ``"model"`` axis
    along dim ``head_dims[i]`` (the attention heads, sharded as the
    parameters and the KV cache are), an entry of None replicates it, and
    the output is split along ``out_head_dim``.  Where ``"model"`` does not
    divide every split dim the launch is replicated instead.  Off a mesh, on
    one device, or already inside ``shard_map``, ``fn`` is called directly.
    ``None`` arguments pass through untouched.
    """
    import jax
    from jax.sharding import AxisType, PartitionSpec as P

    mesh = None
    for a in args:
        m = None if a is None else getattr(jax.typeof(a).sharding, "mesh", None)
        if m is not None and m.size > 1:
            mesh = m
            break
    if mesh is None or AxisType.Manual in tuple(mesh.axis_types):
        return fn(*args)
    model = dict(mesh.shape).get("model", 1)
    split = model > 1 and all(
        a.shape[d] % model == 0 for a, d in zip(args, head_dims) if a is not None and d is not None
    )

    def spec(a, d):
        if not split or d is None:
            return P()
        dims = [None] * a.ndim
        dims[d] = "model"
        return P(*dims)

    keep = [i for i, a in enumerate(args) if a is not None]

    def body(*xs):
        full = list(args)
        for i, x in zip(keep, xs):
            full[i] = x
        return fn(*full)

    out_spec = P(*([None] * out_head_dim), "model") if split else P()
    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(spec(args[i], head_dims[i]) for i in keep),
        out_specs=out_spec, check_vma=False,
    )(*(args[i] for i in keep))
