"""Serve a small MoE model with batched requests: prefill + decode loop with
the control-flow plane's lookahead routing, reporting per-phase latency and
the control-plane byte share.

    PYTHONPATH=src python examples/serve_moe.py --batch 4 --prompt-len 64 --gen 32

``--kernels`` runs the Pallas kernels off-TPU too (interpret mode; on TPU
they always run): grouped-GEMM experts, flash-decode attention and the
plan-steered decode MoE.
``--decode-plane`` serves decode through the Agile decode plane: the next
step's DecodePlan is carried in the KV cache (router runs during the previous
step's FFN), dispatch is capacity-sort-free, and attention reads only the
valid cache prefix — the prefill-shaped machinery never runs per token.
``--spec-tokens N`` decodes speculatively: N tokens per launch through the
vector-steered kernels (per-token cache indices on the scalar-prefetch path),
with greedy verify/rollback — output is identical to sequential decode.
``--draft-tree B1,B2,...`` launches draft *trees* instead of chains (per-depth
branching factors; ngram-filled sibling slots hedge across alternative
continuations): all nodes attend in one ancestor-masked launch sharing the
prefix KV, the verifier walks the tree, and the accepted root path is
compacted into the cache — output is still identical to sequential decode.
``--data D --model M`` serve on a (D, M) device mesh: prefill runs the a2a
expert-parallel strategy and the decode plane executes the cache-carried plan
as per-shard expert slices combined by one psum per MoE layer
(``make_sharded_decode_apply``) — there is no replicated fallback; a model
axis that does not divide the expert count is an error, not a silent
degradation.  The full continuous-batching loop (ragged slots, admission,
telemetry) lives in ``repro.launch.serve`` — which also scales out into the
fault-tolerant elastic fabric (``--fabric N`` data-parallel replicas behind
one admission queue, ``--inject crash@step=7,...`` for deterministic fault
injection with checkpointed re-warm and a speculation-degradation ladder).
"""
import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--kernels", action="store_true",
                    help="run the Pallas kernels off-TPU (interpret mode)")
    ap.add_argument("--decode-plane", action="store_true",
                    help="decode through the Agile decode plane (plan in "
                         "cache, no capacity sort, prefix-only attention)")
    ap.add_argument("--spec-tokens", type=int, default=1,
                    help="speculative width: tokens per decode launch, with "
                         "greedy verify/rollback (1 = plain decode)")
    ap.add_argument("--draft-tree", default="",
                    help="per-depth branching factors for draft trees, e.g. "
                         "'2,2' (implies --decode-plane speculative serve; "
                         "overrides --spec-tokens with the node count)")
    ap.add_argument("--data", type=int, default=1,
                    help="data-parallel mesh axis (batch sharding)")
    ap.add_argument("--model", type=int, default=1,
                    help="model-parallel mesh axis (heads, FFN, experts); "
                         "the decode plane runs plan-sliced psum expert "
                         "parallelism at --model > 1")
    args = ap.parse_args()

    from repro.core.plans import TreePlan

    tree = None
    if args.draft_tree:
        branching = [int(v) for v in args.draft_tree.split(",") if v.strip()]
        tree = TreePlan.from_branching(branching).validate()
        args.spec_tokens = tree.num_nodes
        args.decode_plane = True

    cfg = get_smoke_config("qwen3-moe-235b-a22b")
    if args.kernels:
        cfg = dataclasses.replace(cfg, use_pallas=True)
    if args.decode_plane:
        cfg = dataclasses.replace(cfg, decode_plane=True)
    if args.spec_tokens > 1:
        cfg = dataclasses.replace(cfg, spec_tokens=args.spec_tokens)
    if args.model > 1 and cfg.decode_plane and cfg.num_experts % args.model:
        sys.exit(
            f"--model {args.model} does not divide num_experts="
            f"{cfg.num_experts}: the distributed decode plane shards the "
            "expert stacks over the model axis (there is no replicated "
            "fallback); pick a divisor or --model 1"
        )

    from jax.sharding import NamedSharding

    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_model
    from repro.models import transformer as trf
    from repro.parallel.sharding import batch_spec, cache_shardings, param_shardings

    mesh = make_host_mesh(args.data, args.model)
    B, S = args.batch, args.prompt_len
    # spec decode may write up to T-1 draft rows past the last kept token
    max_len = S + args.gen + max(args.spec_tokens - 1, 0)
    key = jax.random.PRNGKey(0)
    prompts = jax.random.randint(key, (B, S), 0, cfg.vocab_size)

    with mesh:
        model = build_model(cfg, mesh, B)
        params = model.init(key)
        params = jax.device_put(params, param_shardings(params, mesh))
        c_shard = cache_shardings(
            jax.eval_shape(lambda: trf.init_cache(cfg, B, max_len)), B, mesh
        )
        lg1 = NamedSharding(mesh, batch_spec(B, mesh, extra_dims=1))
        prefill = jax.jit(model.prefill, out_shardings=(lg1, c_shard))
        decode = jax.jit(model.decode_step, out_shardings=(lg1, c_shard))

        cache = model.init_cache(B, max_len, shardings=c_shard)
        t0 = time.perf_counter()
        logits, cache = prefill(params, prompts, cache)
        logits.block_until_ready()
        t_prefill = time.perf_counter() - t0
        print(f"prefill: {B}x{S} tokens in {t_prefill*1e3:.1f} ms "
              f"({B*S/t_prefill:.0f} tok/s)")

        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        out = [toks]
        t0 = time.perf_counter()
        if args.spec_tokens > 1:
            # speculative serve: T tokens per launch (repeat-last-token chain
            # drafts, or ngram-filled trees with --draft-tree), greedy verify
            # keeps exactly what sequential decode would emit
            import numpy as np

            from repro.launch.speculative import (
                draft_tree_ngram,
                greedy_accept,
                greedy_accept_tree,
            )

            T = args.spec_tokens
            lgT = NamedSharding(mesh, batch_spec(B, mesh, extra_dims=2))
            spec = jax.jit(
                lambda p, c, t, l, a: model.decode_tokens(p, c, t, l, a, tree=tree),
                out_shardings=(lgT, c_shard),
            )
            commit = jax.jit(model.commit_tree_path, donate_argnums=(0,),
                             out_shardings=c_shard)
            lengths = np.full((B,), S, np.int32)
            prev_accept = np.zeros((B,), np.int32)
            gen_left = np.full((B,), args.gen - 1, np.int32)
            launches = 0
            last = np.array(toks)  # owned copy: updated in the verify loop
            history = [[int(v)] for v in last]
            while (gen_left > 0).any():
                if tree is not None:
                    draft = np.stack(
                        [draft_tree_ngram(history[b], int(last[b]), tree) for b in range(B)]
                    ).astype(np.int32)
                else:
                    draft = np.tile(last[:, None], (1, T)).astype(np.int32)
                logits, cache = spec(params, cache, jnp.asarray(draft),
                                     jnp.asarray(lengths), jnp.asarray(prev_accept))
                launches += 1
                y = np.asarray(jnp.argmax(logits, -1))
                path_pad = np.tile(np.arange(T, dtype=np.int32), (B, 1))
                acc_n = np.zeros((B,), np.int32)
                for b in range(B):
                    if gen_left[b] <= 0:
                        continue
                    if tree is not None:
                        path = greedy_accept_tree(draft[b], y[b], tree, int(gen_left[b]))
                        a = len(path)
                        path_pad[b, :a] = path
                        accepted = [int(y[b, p]) for p in path]
                        prev_accept[b] = path[-1]
                    else:
                        a = greedy_accept(draft[b], y[b], T, int(gen_left[b]))
                        accepted = [int(v) for v in y[b, :a]]
                        prev_accept[b] = a - 1
                    history[b].extend(accepted)
                    acc_n[b] = a
                    gen_left[b] -= a
                    last[b] = accepted[-1]
                if tree is not None and not tree.is_chain():
                    cache = commit(cache, jnp.asarray(lengths), jnp.asarray(path_pad))
                lengths += acc_n
            t_decode = time.perf_counter() - t0
            n_gen = args.gen - 1
            shape = f"tree {args.draft_tree}" if tree is not None else f"width {T}"
            print(f"decode: {launches} speculative launches ({shape}) x {B} seqs "
                  f"in {t_decode*1e3:.1f} ms ({t_decode/max(n_gen,1)*1e3:.1f} ms/token, "
                  f"{n_gen/max(launches,1):.2f} accepted tokens/launch)")
            print("generated token ids (first sequence):", history[0][: args.gen])
            return
        for i in range(args.gen - 1):
            logits, cache = decode(params, cache, toks, jnp.int32(S + i))
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
            out.append(toks)
        jax.block_until_ready(out[-1])
    t_decode = time.perf_counter() - t0
    per_tok = t_decode / (args.gen - 1) * 1e3
    print(f"decode: {args.gen-1} steps x {B} seqs in {t_decode*1e3:.1f} ms "
          f"({per_tok:.1f} ms/token, {B*(args.gen-1)/t_decode:.0f} tok/s)")
    gen = jnp.stack(out, axis=1)
    print("generated token ids (first sequence):", gen[0].tolist())


if __name__ == "__main__":
    main()
