"""Production serving driver: an elastic fabric of continuous-batching serve
replicas over one shared admission queue, with speculative multi-token
launches on the Agile decode plane.

Each replica (:class:`ServeReplica`) is the continuous-batching loop over a
ragged slot pool, factored into a **step-driven, snapshotable** object: every
:meth:`ServeReplica.step` processes ``spec_tokens`` tokens for every slot in
ONE model call (one flash-decode launch and one moe_decode launch per layer —
per-token cache indices ride the scalar-prefetch path as control-word
vectors).  Between launches the replica:

* **verifies** each slot's draft greedily — the accepted prefix is exactly
  what sequential decode would have produced (rollback re-derives nothing:
  rejected cache rows are overwritten by the next launch, and the plan row
  consumed next launch is the one computed from the accepted position's
  route source, carried per draft position in the cache);
* **buffers** accepted tokens per request, publishing them only when the
  request completes — the exactly-once contract the fabric's crash recovery
  rests on (a half-served request is simply re-run; greedy decode being
  deterministic, the re-run is byte-identical);
* aggregates **plan-quality telemetry** (stale-vs-fresh top-k agreement per
  MoE layer) so lookahead-staleness regressions are visible in production
  output.

Admission (queued prompts into finished slots) is supervisor-driven:
:meth:`ServeReplica.admit` runs the shared B=1 admission prefill
(``launch.steps.build_admission``) and writes the slot into the batch cache
sharding-preservingly.

Tree drafts (``--draft-tree B1,B2,...``) and the model-based drafter
(``--drafter model``) ride the same step: the verifier walks the tree
(``greedy_accept_tree``), ``Model.commit_tree_path`` compacts the accepted
root path, and ``prev_accept`` becomes the accepted node index.

Control-word invariants this loop relies on (and must uphold):

* **Plan-row carry** — the plan consumed by a launch's token 0 is the row
  the PREVIOUS launch routed from the accepted node's route source;
  ``prev_accept`` must therefore always be the node index the verifier
  accepted last (chain: accepted count - 1 — the same number).
* **Length-clamp contract** — ``lengths[b]`` is the single source of truth
  for slot b's committed prefix; no launch reads past ``lengths[b] + t``
  for its token t, which is why rejected draft rows (and parked slots fed
  dummy tokens at row 0 depth) can never contaminate a later launch.
* **Rolling-buffer slack** — rolling caches carry ``spec_tokens - 1`` slack
  slots so a launch's later draft writes never evict rows still inside an
  earlier draft token's window; tree drafts are chain-only on rolling
  layers (scattered commits do not compose with modulo addressing).

Distributed decode plane (``--model N``): the cache-carried ``DecodePlan`` is
the distributed control word — plan rows replicate over the model axis, each
shard executes only its resident expert slice and ONE psum per MoE layer
combines the partial outputs.  Everything stays mesh-resident between
launches: sharded cache allocation, pinned shardings, cache donation.

Elastic serve fabric (``--fabric N``): N data-parallel replicas behind one
queue, supervised by :class:`repro.runtime.fabric.ServeFabric` — replica
crashes re-admit in-flight prompts (dedup by request id, no token emitted
twice), transient launch failures retry with bounded exponential backoff,
poisoned prompts are rejected by a per-request retry budget, a rejoining
replica re-warms by replaying admission prefill from the periodic
``CheckpointManager`` snapshot, and a straggling replica descends the
speculation ladder (tree → chain → width 1) before exclusion.  ``--inject``
drives the deterministic fault harness (``repro.runtime.faults``), e.g.
``--inject crash@step=7,launch@step=3:times=2,stall@secs=9:times=4``.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-235b-a22b \
        --smoke --slots 4 --prompt-len 32 --gen 16 --requests 8 \
        --decode-plane --spec-tokens 4 --fabric 2 --inject crash@step=7
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Dict, List, Optional

import numpy as np

from repro.core.programs import compile_program, masked_argmax, program_slots
from repro.runtime.fabric import Request, Result
from repro.runtime.faults import RequestRejected


# host-side draft policies: the tree fillers in launch.speculative (a chain
# is the degenerate tree, so one implementation serves both shapes) plus the
# draft-model policy
DRAFTER_CHOICES = ("model", "ngram", "repeat")


class ServeReplica:
    """One serve replica: the continuous-batching speculative decode loop as
    a resumable object.

    Crash model: ALL of this object (device caches and host slot state) may
    vanish at any point; the supervisor's queue/ledger is the only durable
    record.  Accepted tokens are therefore buffered per request in
    ``emitted`` and only released by :meth:`step` when the request finishes.

    The optional ``fault_hook(replica_id, step, phase, rids)`` is called
    immediately before each launch (``phase="launch"``, ``step`` = 1-based
    launch index) and each admission prefill (``phase="admit"``); it may
    raise :class:`~repro.runtime.faults.ReplicaCrash` /
    :class:`~repro.runtime.faults.TransientLaunchError` or return synthetic
    stall seconds.  Nothing is mutated before the hook runs, so an injected
    failure never leaves a launch half-applied.  A stall at or past
    ``launch_timeout`` raises ``TransientLaunchError`` instead of running —
    the per-launch timeout fails fast with state intact.
    """

    def __init__(
        self,
        cfg,
        mesh,
        slots: int,
        max_len: int,
        params,
        *,
        tree=None,
        drafter: str = "ngram",
        telemetry: bool = False,
        fault_hook=None,
        replica_id: int = 0,
        launch_timeout: Optional[float] = None,
        drafter_key: int = 7,
        steer_drafter: bool = True,
    ):
        import jax
        import jax.numpy as jnp

        from repro.configs.base import ShapeCell
        from repro.core.plans import TreePlan
        from repro.launch.speculative import TREE_DRAFTERS, ModelDrafter
        from repro.launch.steps import build_admission, build_model, build_spec_serve_step
        from repro.parallel.sharding import param_shardings

        self._jnp = jnp
        self.cfg, self.mesh = cfg, mesh
        self.B, self.max_len = slots, max_len
        self.tree = tree
        self.T = max(cfg.spec_tokens, 1)
        self.telemetry = telemetry and cfg.decode_plane and cfg.is_moe
        self.fault_hook = fault_hook
        self.replica_id = replica_id
        self.launch_timeout = launch_timeout
        self._branchy = tree is not None and not tree.is_chain()

        # paged KV plane: physical pages + per-slot block tables replace the
        # contiguous slot stripes; the block table is the control word every
        # launch prefetches, and admission becomes page assignment + a prefix
        # trie probe instead of a stripe copy
        self.paged = bool(cfg.paged)
        self.page_telemetry = bool(telemetry) and self.paged
        self.pager = None
        self.trie = None
        self._pending_commit = None  # (dst, src) maps fused into the NEXT launch
        if self.paged:
            from repro.core.pages import PageTable, PrefixTrie
            from repro.models.transformer import max_pages_for, num_pages

            self.pager = PageTable(
                slots, max_pages_for(cfg, max_len),
                num_pages(cfg, slots, max_len), cfg.page_size,
            )
            self.trie = PrefixTrie(cfg.page_size)
            self.pages_shared_total = 0
            self.admissions_paged = 0
            self.admit_copy_rows = 0
            self.trie_nodes_created = 0
        with mesh:
            bundle = build_spec_serve_step(
                cfg, mesh, ShapeCell("d", max_len, slots, "decode"),
                telemetry=self.telemetry, tree=tree,
            )
            self.model = bundle.model
            self._c_shard = bundle.in_shardings[1]
            self.params = jax.device_put(params, bundle.in_shardings[0])
            # the serving cache is allocated directly with its mesh layout and
            # never leaves it: the decode step donates it in place, and
            # admission writes prefilled slots into it sharding-preservingly
            self.cache = self.model.init_cache(slots, max_len, shardings=self._c_shard)
            adm = build_admission(cfg, mesh, self.model, max_len, self._c_shard)
            self._prefill, self._one_cache_init, self._admit = (
                adm.prefill, adm.one_cache_init, adm.admit,
            )
            self._decode = bundle.jit()
            # paged tree commit is pointer rewiring fused into the next
            # launch's (dst, src) control words — no row-compaction launch
            self._commit = (
                jax.jit(self.model.commit_tree_path, donate_argnums=(0,),
                        out_shardings=self._c_shard)
                if tree is not None and not self.paged
                else None
            )
            self._drafter = None
            if drafter == "model" and self.T > 1:
                # same family, one layer, width-1 launches: the draft model
                # rides the identical decode plane (and admission path)
                # the 1-layer draft model keeps its own contiguous cache —
                # it never shares pages with the target pool
                draft_cfg = dataclasses.replace(
                    cfg, num_layers=1, spec_tokens=1, paged=False
                )
                draft_model = build_model(draft_cfg, mesh, slots)
                dp = draft_model.init(jax.random.PRNGKey(drafter_key))
                dp = jax.device_put(dp, param_shardings(dp, mesh))
                self._drafter = ModelDrafter(draft_model, dp, slots, max_len)
            self._propose_tree = tree if tree is not None else TreePlan.chain(self.T)
            self._tree_fill = TREE_DRAFTERS.get(drafter, TREE_DRAFTERS["ngram"])

        # host-side slot state (the ragged-batch control words)
        B = slots
        self.lengths = np.zeros((B,), np.int32)
        self.prev_accept = np.zeros((B,), np.int32)
        self.last_tok = np.zeros((B,), np.int32)
        self.gen_left = np.zeros((B,), np.int32)
        self.active = np.zeros((B,), bool)
        self.history: List[List[int]] = [[] for _ in range(B)]
        self.requests: List[Optional[Request]] = [None] * B
        self.emitted: List[List[int]] = [[] for _ in range(B)]

        # request-level control-flow plane: per-slot compiled program +
        # automaton state.  ``prog_state[b]`` is the state after slot b's
        # full emitted stream; ``prog_rows[b, p]`` mirrors it per committed
        # stream position (rollback-exact: only accepted positions are ever
        # written, exactly like the KV rows they ride next to).  Fork groups
        # track the K branch slots serving one request until join.
        self.steer_drafter = bool(steer_drafter)
        self.programs: List[Optional[Any]] = [None] * B
        self.prog_state = np.full((B,), -1, np.int32)
        self.prog_rows = np.full((B, max_len + 1), -1, np.int32)
        self.fork_branch = np.full((B,), -1, np.int32)
        self.forks: Dict[int, dict] = {}
        self._prog_cache: Dict[str, Any] = {}
        self.prog_states_seen: set = set()
        self.prog_tokens = 0
        self.prog_mask_frac_sum = 0.0
        self.prog_mask_cnt = 0
        self.prog_masked_emissions = 0  # emitted tokens outside the mask: MUST stay 0
        self.forks_started = 0
        self.forks_live_max = 0
        self.fork_kv_rows_copied = 0

        self.steps = 0            # launch counter — the fault-spec step index
        self.launches = 0
        self.prefills = 0
        self.accepted_total = 0
        self.drafted_total = 0
        self.accept_hist = np.zeros((self.T + 1,), np.int64)
        self.agreements: List[float] = []
        self.prefill_ms = 0.0
        self.last_stall = 0.0

    # ------------------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [b for b in range(self.B) if not self.active[b]]

    def in_flight(self) -> List[Request]:
        """Requests currently being served, in slot order (= admission order
        for the supervisor's front-of-queue re-admission on crash).  Fork
        branches share one request, which must requeue exactly ONCE — its
        program spec rides the Request, so re-admission re-forks from
        scratch and the deterministic re-run stays byte-identical."""
        out: List[Request] = []
        seen: set = set()
        for r in self.requests:
            if r is not None and r.rid not in seen:
                seen.add(r.rid)
                out.append(r)
        return out

    def has_work(self) -> bool:
        return bool(self.active.any())

    def snapshot_meta(self) -> dict:
        """JSON-serializable slot metadata for the fabric's checkpoint: the
        admission ledger a rejoining replica replays prefill from.  Under the
        paged plane this also snapshots the block table + refcounts and the
        prefix trie — page allocation is deterministic (lowest free id), so a
        re-warm replay of the ledger reproduces the snapshot byte-for-byte,
        and the snapshot itself round-trips through ``PageTable.from_snapshot``
        / ``PrefixTrie.from_snapshot`` for direct restore."""
        meta = {
            "steps": int(self.steps),
            "rids": [int(r.rid) for r in self.requests if r is not None],
            "lengths": [int(v) for v in self.lengths],
        }
        if self.paged:
            meta["pager"] = self.pager.snapshot()
            meta["trie"] = self.trie.snapshot()
        progs = {
            str(b): {"state": int(self.prog_state[b]),
                     "branch": int(self.fork_branch[b]),
                     "emitted": len(self.emitted[b])}
            for b in range(self.B)
            if self.active[b] and self.programs[b] is not None
        }
        if progs:
            # informational ledger entry: automaton state is DERIVED state
            # (recomputable from the emitted stream), so re-warm replays the
            # requeued request's program rather than restoring these words —
            # but the ledger records them so a snapshot pins what the crash
            # interrupted
            meta["programs"] = progs
        return meta

    def paged_stats(self) -> dict:
        """Page-pool telemetry: occupancy, sharing, fragmentation."""
        live = [int(l) for l, a in zip(self.lengths, self.active) if a]
        return {
            "occupancy": self.pager.occupancy(),
            "allocated_pages": self.pager.allocated_pages(),
            "fragmentation": self.pager.fragmentation(live),
            "pages_shared_total": int(self.pages_shared_total),
            "admissions": int(self.admissions_paged),
            "pages_shared_per_admission": (
                self.pages_shared_total / max(self.admissions_paged, 1)
            ),
            "admit_copy_rows": int(self.admit_copy_rows),
            "trie_nodes": int(self.trie.nodes),
        }

    # ------------------------------------------------------------------
    def _bind_pages(self, b: int, prompt: np.ndarray) -> np.ndarray:
        """Paged admission = page assignment + trie probe, never a stripe copy.

        Probe the prefix trie for full pages already holding this prompt's KV
        (``probe`` increfs the matches for us), bind them directly into slot
        ``b``'s block-table row, allocate private pages for the remainder, and
        publish the prompt's own full pages for future requests.  Returns the
        ``(max_len,)`` physical-row vector for the admission scatter: shared
        positions (and positions past the prompt) carry the out-of-range
        sentinel so their writes drop — a trie-resident prompt admits with
        ZERO KV rows copied.  Generation writes land at positions >=
        ``len(prompt)``, which shared pages (full prompt pages only) never
        cover, so sharing needs no copy-on-write on this path."""
        ps = self.cfg.page_size
        pager, trie = self.pager, self.trie
        evict = lambda: trie.evict_one(pager)
        L = len(prompt)
        shared = trie.probe(prompt, pager)
        for i, page in enumerate(shared):
            pager.table[b, i] = page  # probe already took our reference
        pager.ensure(b, max(L, 1), evict=evict)
        self.trie_nodes_created += trie.insert(
            prompt, [int(pager.table[b, i]) for i in range(L // ps)], pager
        )
        sentinel = pager.num_pages * ps  # positive OOB: scatter drops, never wraps
        rows = np.full((self.max_len,), sentinel, np.int32)
        for pos in range(len(shared) * ps, L):
            rows[pos] = int(pager.table[b, pos // ps]) * ps + pos % ps
        self.pages_shared_total += len(shared)
        self.admit_copy_rows += max(L - len(shared) * ps, 0)
        self.admissions_paged += 1
        return rows

    # ------------------------------------------------------------------
    def _compiled_program(self, spec: Optional[dict]):
        """Compile (and cache) a request's program spec; specs are small
        JSON dicts, so the cache key is their canonical dump."""
        if not spec:
            return None
        key = json.dumps(spec, sort_keys=True)
        prog = self._prog_cache.get(key)
        if prog is None:
            prog = compile_program(spec, self.cfg.vocab_size)
            self._prog_cache[key] = prog
        return prog

    def admit(self, req: Request) -> int:
        """Prefill ``req`` into a free slot; returns the (first) slot index.

        Raises :class:`RequestRejected` for prompts that can never finish
        within the slot budget (checked BEFORE any launch), and lets the
        fault hook veto the admission (poisoned prompts) while no state has
        been touched.

        A request carrying a fork program admits into ``fork`` slots off ONE
        shared admission prefill: every branch writes the same prefilled
        prompt (under the paged plane branch 0 publishes the prompt's full
        pages to the prefix trie and later branches bind them by pointer —
        zero KV rows copied per fork), and branch ``i``'s first token is the
        ``i``-th best *allowed* token of the prefill logits, so the K
        continuations diverge at the fork point and nowhere earlier.
        """
        jnp = self._jnp
        spec = getattr(req, "program", None)
        try:
            prog = self._compiled_program(spec)
        except ValueError as err:
            raise RequestRejected(f"bad program spec: {err}", rid=req.rid)
        k = prog.fork if prog is not None else 1
        if len(req.prompt) + req.gen + self.T > self.max_len:
            raise RequestRejected(
                f"prompt len {len(req.prompt)} + gen {req.gen} + spec width "
                f"{self.T} exceeds the slot budget {self.max_len}",
                rid=req.rid,
            )
        if k > self.B:
            raise RequestRejected(
                f"program forks {k} ways but the replica has {self.B} slots",
                rid=req.rid,
            )
        if prog is not None and len(prog.automaton.allowed(prog.automaton.start)) < k:
            raise RequestRejected(
                f"program forks {k} ways but its grammar allows only "
                f"{len(prog.automaton.allowed(prog.automaton.start))} first tokens",
                rid=req.rid,
            )
        free = self.free_slots()
        if len(free) < k:
            raise RuntimeError(
                f"replica {self.replica_id}: no free slot "
                f"({k} needed, {len(free)} available)"
            )
        if self.fault_hook is not None:
            self.fault_hook(self.replica_id, self.steps + 1, "admit", (req.rid,))
        slots = free[:k]
        t0 = time.perf_counter()
        prompt_np = np.asarray(req.prompt, np.int32)
        prompt = jnp.asarray(prompt_np)
        with self.mesh:
            one = self._one_cache_init()
            if self.cfg.frontend:
                fe = jnp.zeros(
                    (1, self.cfg.frontend_tokens, self.cfg.frontend_dim), jnp.bfloat16
                )
                logits1, one = self._prefill(self.params, prompt[None], one, fe)
            else:
                logits1, one = self._prefill(self.params, prompt[None], one)
            for i, b in enumerate(slots):
                copied0 = self.admit_copy_rows if self.paged else 0
                if self.paged:
                    rows = self._bind_pages(b, prompt_np)
                    self.cache = self._admit(self.cache, one, b, jnp.asarray(rows))
                else:
                    self.cache = self._admit(self.cache, one, b)
                if i > 0:
                    self.fork_kv_rows_copied += (
                        self.admit_copy_rows - copied0 if self.paged
                        else len(prompt_np)
                    )
        self.prefill_ms += (time.perf_counter() - t0) * 1e3
        self.prefills += 1
        lg1 = np.asarray(logits1[0])
        if prog is not None:
            auto = prog.automaton
            mask = auto.mask(auto.start)
            neg = np.finfo(np.float32).min
            order = np.argsort(-np.where(mask, lg1.astype(np.float32), neg),
                               kind="stable")
            firsts = [int(order[i]) for i in range(k)]
            self.prog_mask_cnt += k
            self.prog_mask_frac_sum += k * (1.0 - float(mask.mean()))
            self.prog_states_seen.add(int(auto.start))
        else:
            firsts = [int(np.argmax(lg1))]
        if k > 1:
            self.forks[req.rid] = {
                "req": req, "k": k, "join": prog.join,
                "streams": {}, "accepted": {}, "retired": set(),
            }
            self.forks_started += 1
            self.forks_live_max = max(
                self.forks_live_max,
                sum(1 for b in range(self.B)
                    if self.active[b] and self.fork_branch[b] >= 0) + k,
            )
        for i, b in enumerate(slots):
            first = firsts[i]
            self.lengths[b] = len(req.prompt)
            self.last_tok[b] = first
            self.prev_accept[b] = 0
            self.gen_left[b] = req.gen
            self.active[b] = True
            self.history[b] = [first]
            self.requests[b] = req
            self.emitted[b] = [first]
            self.programs[b] = prog
            self.fork_branch[b] = i if k > 1 else -1
            if prog is not None:
                st = prog.automaton.step(prog.automaton.start, first)
                if st < 0:
                    self.prog_masked_emissions += 1
                self.prog_state[b] = st
                self.prog_rows[b] = -1
                self.prog_rows[b, len(req.prompt)] = st
                self.prog_states_seen.add(int(st))
                self.prog_tokens += 1
            else:
                self.prog_state[b] = -1
            if self._drafter is not None:
                self._drafter.admit(b, prompt)
        return slots[0]

    # ------------------------------------------------------------------
    def step(self) -> List[Result]:
        """One speculative launch over the ragged pool: draft, decode,
        greedy verify/rollback, tree commit; returns the requests that
        completed this step (their full buffered token streams)."""
        if not self.active.any():
            return []
        jnp = self._jnp
        from repro.launch.speculative import greedy_accept_tree

        step_no = self.steps + 1
        self.last_stall = 0.0
        if self.fault_hook is not None:
            from repro.runtime.faults import TransientLaunchError

            rids = tuple(r.rid for r in self.requests if r is not None)
            stall = float(self.fault_hook(self.replica_id, step_no, "launch", rids) or 0.0)
            if self.launch_timeout is not None and stall >= self.launch_timeout:
                # fail fast BEFORE the launch: state is never half-mutated
                raise TransientLaunchError(
                    f"launch exceeded the {self.launch_timeout:.1f}s timeout "
                    f"(stalled {stall:.1f}s)"
                )
            self.last_stall = stall
        self.steps = step_no

        T, B = self.T, self.B
        # ---- draft: one launch's tokens for every slot ---------------------
        # a chain is the degenerate tree, so ONE fill path serves both shapes.
        # Program-constrained slots steer every drafter by the automaton's
        # allowed set (the draft model through logit masks, the host
        # heuristics through a post-fill clamp): drafting a token the masked
        # verifier must reject is a wasted node, so constraints RAISE accept
        # rates rather than fighting speculation.
        def _guide(b):
            if not self.steer_drafter or not self.active[b]:
                return None
            prog = self.programs[b]
            if prog is None:
                return None
            return (prog.automaton, int(self.prog_state[b]))

        if self._drafter is not None and T > 1:
            self._drafter.catch_up()
            guides = [_guide(b) for b in range(B)]
            toks = self._drafter.propose(
                self.last_tok, self.lengths, self._propose_tree,
                guides if any(g is not None for g in guides) else None,
            )
        else:
            toks = np.zeros((B, T), np.int32)
            for b in range(B):
                if self.active[b] and T > 1:
                    toks[b] = self._tree_fill(
                        self.history[b], int(self.last_tok[b]), self._propose_tree
                    )
        toks[:, 0] = self.last_tok
        if T > 1 and self.steer_drafter:
            from repro.launch.speculative import steer_tree_tokens

            for b in range(B):
                g = _guide(b)
                if g is not None and g[1] >= 0:
                    toks[b] = steer_tree_tokens(
                        toks[b], self._propose_tree, g[0], g[1], self.history[b]
                    )

        # ---- one speculative launch over the ragged pool -------------------
        if self.paged:
            # grow each active slot's block table to cover this launch's
            # writes BEFORE prefetch; the table is the launch's control word
            evict = lambda: self.trie.evict_one(self.pager)
            for b in range(B):
                if self.active[b]:
                    self.pager.ensure(b, int(self.lengths[b]) + T, evict=evict)
        with self.mesh:
            if self.paged and self._branchy:
                # previous step's accepted tree path rides in as (dst, src)
                # row-move maps, applied at the top of this launch (fused
                # commit: zero extra launches); identity (-1) on step one
                dst, src = (
                    self._pending_commit
                    if self._pending_commit is not None
                    else (np.full((B, T), -1, np.int32),) * 2
                )
                out = self._decode(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(self.lengths), jnp.asarray(self.prev_accept),
                    jnp.asarray(self.pager.table), jnp.asarray(dst),
                    jnp.asarray(src),
                )
            elif self.paged:
                out = self._decode(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(self.lengths), jnp.asarray(self.prev_accept),
                    jnp.asarray(self.pager.table),
                )
            else:
                out = self._decode(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(self.lengths), jnp.asarray(self.prev_accept),
                )
        if self.telemetry:
            logits, self.cache, metrics = out
            self.agreements.append(float(metrics["plan_agreement"]))
        else:
            logits, self.cache = out
        self.launches += 1
        # np.array (not asarray): programmed slots overwrite rows with the
        # masked argmax, and jax buffers view as read-only
        y = np.array(jnp.argmax(logits, -1))  # (B, T) verified tokens

        # ---- constraint masks inside the verify step -----------------------
        # per draft node, the automaton state implied by the node's root-path
        # draft tokens selects the allowed set its emission is masked with;
        # along the accepted path draft tokens ARE the emitted stream, so the
        # masked emissions equal what a sequential masked loop would produce
        lg = None
        parents = self._propose_tree.parents
        for b in range(B):
            prog = self.programs[b]
            if prog is None or not self.active[b]:
                continue
            auto = prog.automaton
            if auto.is_accept(int(self.prog_state[b])):
                continue  # stream already complete: nothing to emit
            if lg is None:
                lg = np.asarray(logits)  # (B, T, V), pulled once per launch
            A = auto.tree_states(int(self.prog_state[b]), toks[b], parents)
            for t in range(T):
                st = int(A[t])
                if st < 0 or auto.is_accept(st):
                    continue  # unreachable node (or past the stop): don't-care
                m = auto.mask(st)
                y[b, t] = masked_argmax(lg[b, t], m)
                self.prog_mask_cnt += 1
                self.prog_mask_frac_sum += 1.0 - float(m.mean())

        # ---- greedy verify / rollback --------------------------------------
        path_pad = np.tile(np.arange(T, dtype=np.int32), (B, 1))
        acc_n = np.zeros((B,), np.int32)
        prog_done = np.zeros((B,), bool)
        for b in range(B):
            if not self.active[b]:
                self.lengths[b] = 0  # park finished slots at depth 0
                continue
            prog = self.programs[b]
            if prog is not None and prog.automaton.is_accept(int(self.prog_state[b])):
                # accepted at admission (single-token grammar): emit nothing
                prog_done[b] = True
                continue
            if prog is not None:
                from repro.launch.speculative import accept_tree_program

                path, _, fin = accept_tree_program(
                    toks[b], y[b], self._propose_tree, int(self.gen_left[b]),
                    prog.automaton, int(self.prog_state[b]),
                )
                prog_done[b] = fin
            else:
                path = greedy_accept_tree(
                    toks[b], y[b], self._propose_tree, int(self.gen_left[b])
                )
            a = len(path)
            path_pad[b, :a] = path
            accepted = [int(y[b, p]) for p in path]
            self.prev_accept[b] = path[-1]
            if self._drafter is not None:
                # rows [lengths, lengths + a) of the true stream are the
                # launch input followed by all but the last accepted token
                self._drafter.observe(b, [int(self.last_tok[b])] + accepted[:-1])
            self.history[b].extend(accepted)
            self.emitted[b].extend(accepted)
            if prog is not None:
                # advance the carried automaton state by the accepted
                # emissions only (rollback-exact: rejected nodes never touch
                # it) and mirror it per committed stream position
                auto = prog.automaton
                st = int(self.prog_state[b])
                for i, tok in enumerate(accepted):
                    if st < 0 or auto.trans[st, tok] < 0:
                        self.prog_masked_emissions += 1
                    st = auto.step(st, tok)
                    self.prog_rows[b, int(self.lengths[b]) + 1 + i] = st
                    self.prog_states_seen.add(int(st))
                self.prog_state[b] = st
                self.prog_tokens += a
            self.accepted_total += a
            self.drafted_total += T
            self.accept_hist[a] += 1
            acc_n[b] = a
            self.gen_left[b] -= a
            self.last_tok[b] = accepted[-1]
        if self._branchy:
            if self.paged:
                # pointer-rewired commit: derive (dst, src) row-move maps from
                # the PRE-accept lengths; they are consumed by the NEXT launch
                # (fused at the top of each layer, before its new writes)
                from repro.core.pages import commit_maps

                self._pending_commit = commit_maps(
                    self.lengths, path_pad, acc_n, T
                )
            else:
                # commit BEFORE advancing lengths: the accepted nodes move
                # from scattered rows base+u_i to contiguous rows base+i
                with self.mesh:
                    self.cache = self._commit(
                        self.cache, jnp.asarray(self.lengths), jnp.asarray(path_pad)
                    )
        done: List[Result] = []
        for b in range(B):
            if not self.active[b]:
                continue
            self.lengths[b] += acc_n[b]
            if (
                prog_done[b]
                or self.gen_left[b] <= 0
                or self.lengths[b] + T > self.max_len
            ):
                req = self.requests[b]
                if self.fork_branch[b] >= 0:
                    # a fork branch never publishes alone: record its stream
                    # in the group and let the join policy pick the result
                    grp = self.forks[req.rid]
                    i = int(self.fork_branch[b])
                    grp["streams"][i] = list(self.emitted[b])
                    grp["accepted"][i] = bool(prog_done[b])
                else:
                    done.append(Result(
                        rid=req.rid, tokens=list(self.emitted[b]),
                        replica=self.replica_id,
                    ))
                self._retire_slot(b)
        for rid in list(self.forks):
            res = self._maybe_resolve_fork(rid)
            if res is not None:
                done.append(res)
        if self.page_telemetry:
            stp = self.paged_stats()
            print(f"[replica {self.replica_id} step {self.steps}] paged: "
                  f"occupancy {stp['occupancy']:.2f} "
                  f"({stp['allocated_pages']} pages), shared/admission "
                  f"{stp['pages_shared_per_admission']:.2f}, fragmentation "
                  f"{stp['fragmentation']:.3f}")
        return done

    # ------------------------------------------------------------------
    def _retire_slot(self, b: int) -> None:
        """Release slot ``b``: host control words reset, pages recycled, the
        slot's pending fused-commit row voided (its freed pages may be
        re-bound before the next launch)."""
        self.active[b] = False
        self.requests[b] = None
        self.emitted[b] = []
        self.programs[b] = None
        self.prog_state[b] = -1
        self.fork_branch[b] = -1
        if self.paged:
            self.pager.free_slot(b)
            if self._pending_commit is not None:
                self._pending_commit[0][b] = -1
                self._pending_commit[1][b] = -1

    def _maybe_resolve_fork(self, rid: int) -> Optional[Result]:
        """Join/stop for one fork group.

        ``join="first"``: the winner is the branch whose ACCEPTED stream is
        shortest (ties to the lowest branch index) — a pure function of the
        branch streams, so the outcome is identical across chain, tree,
        paged, and quantized planes.  A live branch already too long to beat
        the best accepted stream can never win and is retired on the spot,
        recycling its slot and pages.  ``join="all"`` runs every branch to
        completion and publishes all streams (concatenated in branch order,
        with the per-branch split in ``Result.branches``).
        """
        grp = self.forks[rid]
        k, join = grp["k"], grp["join"]
        streams, acc = grp["streams"], grp["accepted"]
        if join == "first":
            wins = [(len(streams[i]), i) for i in streams if acc.get(i)]
            if wins:
                best = min(wins)
                for b in range(self.B):
                    if (
                        self.active[b]
                        and self.requests[b] is not None
                        and self.requests[b].rid == rid
                    ):
                        j = int(self.fork_branch[b])
                        # to win, branch j must still accept at a length
                        # >= emitted+1; retire it the moment that bound
                        # can no longer beat ``best``
                        if (len(self.emitted[b]) + 1, j) > best:
                            grp["retired"].add(j)
                            self._retire_slot(b)
        if len(streams) + len(grp["retired"]) < k:
            return None
        cands = [i for i in streams if acc.get(i)] or sorted(streams)
        win = min(cands, key=lambda i: (len(streams[i]), i))
        del self.forks[rid]
        if join == "all":
            ordered = [streams[i] for i in sorted(streams)]
            return Result(
                rid=rid, tokens=[t for s in ordered for t in s],
                replica=self.replica_id, branches=ordered,
            )
        return Result(
            rid=rid, tokens=list(streams[win]), replica=self.replica_id,
        )


# ---------------------------------------------------------------------------
# fabric assembly (shared by the CLI and the fault-tolerance tests)
# ---------------------------------------------------------------------------


def degrade_ladder(tree, spec_width: int) -> List[Any]:
    """The speculation ladder a flagged replica descends: ``(tree, width)``
    per level — full tree, then the chain of its spine, then width 1 (the
    control plane de-configuring itself before the fabric excludes)."""
    ladder = []
    if tree is not None and not tree.is_chain():
        ladder.append((tree, tree.num_nodes))
        chain_w = len(tree.spine())
    else:
        chain_w = spec_width
    if chain_w > 1:
        ladder.append((None, chain_w))
    ladder.append((None, 1))
    return ladder


def make_replica_factory(
    cfg,
    mesh,
    slots: int,
    max_len: int,
    params,
    ladder,
    *,
    drafter: str = "ngram",
    telemetry: bool = False,
    fault_hook=None,
    launch_timeout: Optional[float] = None,
    ckpt=None,
    shrink_to: Optional[tuple] = None,
):
    """Build the fabric's replica factory.

    On a re-warm rebuild the supervisor passes the checkpoint-restored params
    (``params_`` below); a crash flagged as device loss (``shrunk``) rebuilds
    through :func:`repro.runtime.elastic.reshard_serve_after_failure` on the
    shrunken ``shrink_to = (n_healthy, model_axis)`` mesh when a committed
    checkpoint exists — the model axis stays fixed, the data axis shrinks,
    and params are re-placed with the new mesh's serve shardings.
    """

    def make(replica_id: int, level: int, params_=None, shrunk: bool = False):
        from repro.configs.base import ShapeCell

        tr, width = ladder[min(level, len(ladder) - 1)]
        cfg_l = dataclasses.replace(cfg, spec_tokens=width)
        m, p = mesh, params_ if params_ is not None else params
        if shrunk and shrink_to is not None and ckpt is not None and ckpt.latest_step() is not None:
            from repro.runtime.elastic import reshard_serve_after_failure

            n_healthy, model_axis = shrink_to
            state = reshard_serve_after_failure(
                cfg_l, ShapeCell("d", max_len, slots, "decode"), ckpt,
                n_healthy=n_healthy, model_axis=model_axis,
            )
            m, p = state.mesh, state.params
        return ServeReplica(
            cfg_l, m, slots, max_len, p, tree=tr, drafter=drafter,
            telemetry=telemetry, fault_hook=fault_hook, replica_id=replica_id,
            launch_timeout=launch_timeout,
        )

    return make


def _dump_tokens(args, results) -> None:
    """Write {rid: token stream} JSON for cross-run stream-identity diffs.

    CI runs the same request set through two planes (e.g. quantized
    paged+tree vs quantized width-1 contiguous) and diffs the dumps — the
    serve loop's verify/rollback makes both equal the model's sequential
    greedy stream, so any drift is a correctness regression, not noise.
    """
    if not getattr(args, "dump_tokens", ""):
        return
    import json

    with open(args.dump_tokens, "w") as f:
        json.dump(
            {str(rid): list(map(int, r.tokens)) for rid, r in sorted(results.items())},
            f,
        )


def run_cross_process(args, cfg, requests, params, specs, ckpt, *,
                      spec_width, branching, max_len) -> int:
    """Serve through the cross-process fabric: real OS worker processes,
    heartbeat liveness, deadline-aware admission, checkpoint re-warm.

    Returns a process exit code: nonzero on any unanswered, dropped, or
    duplicated rid, or on error results that no injected fault / deadline /
    backpressure setting explains — a zero exit IS the exactly-once
    assertion CI relies on.
    """
    from repro.runtime.fabric import CrossProcessFabric, XFabricConfig
    from repro.runtime.transport import MonotonicClock, make_process_spawn

    clock = MonotonicClock()
    if args.deadline > 0:
        t0 = clock.now()
        for req in requests:
            req.deadline = t0 + args.deadline
    spec_base = dict(
        kind="serve", arch=args.arch, smoke=args.smoke,
        decode_plane=cfg.decode_plane, spec_tokens=spec_width,
        draft_tree=branching, paged=cfg.paged, page_size=cfg.page_size,
        kv_dtype=cfg.kv_dtype, expert_dtype=cfg.expert_dtype,
        drafter=args.drafter, slots=args.slots, max_len=max_len, seed=0,
        faults=args.inject, launch_timeout=args.launch_timeout,
        ckpt_dir=str(ckpt.dir) if ckpt is not None else None,
        heartbeat_every=args.heartbeat_every,
    )
    fabric = CrossProcessFabric(
        make_process_spawn(spec_base), requests,
        XFabricConfig(
            workers=args.workers,
            slots_per_worker=args.slots,
            heartbeat_every=args.heartbeat_every,
            heartbeat_miss_limit=args.heartbeat_miss_limit,
            # boot holiday covers interpreter start + jax import; the worker's
            # heartbeat thread starts before the model build, so compile time
            # needs no headroom here
            spawn_grace=60.0,
            poll_every=min(args.heartbeat_every / 2, 0.1),
            queue_limit=args.queue_limit,
            checkpoint_every=50 if ckpt is not None else 0,
        ),
        clock=clock, specs=specs, ckpt=ckpt, params=params,
    )
    t0 = clock.now()
    results = fabric.run()
    wall = clock.now() - t0
    _dump_tokens(args, results)

    st = fabric.stats
    finished = sum(1 for r in results.values() if r.error is None)
    print(f"served {finished}/{len(requests)} requests across {args.workers} "
          f"worker processes ({args.slots} slots each): {st['accepted']} tokens "
          f"in {wall:.1f} s ({st['launches']} launches, {st['admitted']} "
          f"admissions)")
    print(f"xproc fabric: {st['kills']} kills, {st['heartbeat_misses']} "
          f"heartbeat misses, {st['spawns']} spawns ({st['restores']} "
          f"checkpoint re-warms), {st['requeued']} re-queued, "
          f"{st['deadline_expired']} deadline-expired, "
          f"{st['backpressure_rejects']} backpressure-rejected, "
          f"{st['transient_failures']} transient, {st['poisoned']} poisoned, "
          f"{st['stale_messages']} stale messages dropped, "
          f"{st['dropped']} dropped, {st['duplicates']} duplicates")

    unanswered = [r.rid for r in requests if r.rid not in results]
    errors = [r for r in results.values() if r.error is not None]
    expected_errors = (
        any(s.kind == "poison" for s in specs)
        or args.deadline > 0
        or args.queue_limit > 0
    )
    code = 0
    if unanswered:
        print(f"FABRIC ERROR: {len(unanswered)} requests unanswered: {unanswered}")
        code = 1
    if errors and not expected_errors:
        print(f"FABRIC ERROR: {len(errors)} requests errored without an "
              f"explaining fault/deadline/queue-limit: "
              f"{[(r.rid, r.error) for r in errors]}")
        code = 1
    if st["duplicates"] or st["dropped"]:
        print(f"FABRIC ERROR: {st['duplicates']} duplicate / "
              f"{st['dropped']} dropped results")
        code = 1
    if getattr(args, "program", ""):
        print(f"programs: {st['prog_tokens']} constrained tokens, "
              f"{st['forks_started']} forks, {st['fork_kv_rows_copied']} "
              f"KV rows copied at fork, {st['prog_masked_emissions']} "
              f"masked emissions")
        if st["prog_masked_emissions"]:
            print(f"FABRIC ERROR: {st['prog_masked_emissions']} tokens "
                  f"emitted outside their automaton's allowed set")
            code = 1
    return code


def main(argv: Optional[List[str]] = None) -> Dict[str, Any]:
    """Serve the synthetic request queue described by ``argv`` (the command
    line when None) and return the run's report: ``code`` is 0 iff every
    exit guard held (all requests answered, no unexplained error, no
    duplicate, no masked emission), plus the fabric's counters."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4,
                    help="decode slot pool size PER REPLICA")
    ap.add_argument("--prompt-len", type=int, default=64,
                    help="max synthetic prompt length (prompts arrive ragged)")
    ap.add_argument("--gen", type=int, default=16, help="tokens to generate per request")
    ap.add_argument("--requests", type=int, default=0,
                    help="number of queued requests (default 2x slots x replicas)")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--decode-plane", action="store_true",
                    help="serve decode through the Agile decode plane (plan "
                         "carried in the cache, capacity-sort-free dispatch, "
                         "valid-prefix attention)")
    ap.add_argument("--spec-tokens", type=int, default=1,
                    help="speculative width: tokens per decode launch "
                         "(1 = plain decode)")
    ap.add_argument("--draft-tree", default="",
                    help="comma-separated per-depth branching factors for "
                         "draft TREES, e.g. '2,2,1' (first child continues "
                         "the spine); overrides --spec-tokens with the node "
                         "count")
    ap.add_argument("--paged", action="store_true",
                    help="serve KV through the paged plane: fixed-size "
                         "physical pages + per-slot block tables as the "
                         "scalar-prefetch control word (admission = page "
                         "assignment + prefix-trie probe, tree commit = "
                         "pointer rewiring fused into the next launch)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV rows per physical page (0 = config default)")
    ap.add_argument("--kv-dtype", choices=("", "int8"), default="",
                    help="store KV cache pages in int8 with per-token scale "
                         "control words on the scalar-prefetch path (4x "
                         "decode KV bandwidth; dequant happens in-kernel)")
    ap.add_argument("--expert-dtype", choices=("", "int8"), default="",
                    help="serve decode through pre-quantized int8 expert "
                         "stacks with per-expert scale control words "
                         "(prefill/verify math keeps the f32 stacks)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a shared synthetic system prompt of this "
                         "many tokens to every request (exercises cross-"
                         "request prefix sharing under --paged)")
    ap.add_argument("--expect-shared-pages", action="store_true",
                    help="exit nonzero unless at least one page was shared "
                         "across admissions (CI guard for --paged runs)")
    ap.add_argument("--drafter", choices=sorted(DRAFTER_CHOICES),
                    default="ngram",
                    help="draft policy: host heuristics (repeat/ngram) or a "
                         "small draft model batched through the same decode "
                         "plane")
    ap.add_argument("--telemetry", action="store_true",
                    help="report stale-vs-fresh plan top-k agreement per launch")
    ap.add_argument("--dump-tokens", default="",
                    help="write {rid: token stream} JSON here after the run "
                         "(CI diffs two runs for stream identity)")
    ap.add_argument("--program", default="",
                    help="request control-flow program applied to every "
                         "request: inline JSON spec or @path/to/spec.json "
                         "(automaton segments of kind json_schema / literal "
                         "/ tokens, optional \"fork\": K and \"join\"); "
                         "compiled to flat int32 token-automaton tables by "
                         "repro.core.programs and enforced inside verify")
    ap.add_argument("--fabric", type=int, default=1,
                    help="number of data-parallel serve replicas behind the "
                         "shared admission queue")
    ap.add_argument("--inject", default="",
                    help="deterministic fault specs, e.g. 'crash@step=7,"
                         "launch@step=3:times=2,stall@secs=9:times=4,"
                         "poison@rid=0' (see repro.runtime.faults)")
    ap.add_argument("--launch-timeout", type=float, default=30.0,
                    help="per-launch timeout in seconds; a stalled launch "
                         "fails fast as a transient error and is retried "
                         "with backoff")
    ap.add_argument("--checkpoint-dir", default="",
                    help="fabric snapshot directory (params + admission "
                         "ledger); defaults to a temp dir when faults are "
                         "injected")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="rounds between fabric snapshots (0 = off; "
                         "defaults to 4 when --inject is set)")
    ap.add_argument("--workers", type=int, default=0,
                    help="serve through REAL OS worker processes (cross-"
                         "process fabric): N heartbeat-supervised replicas "
                         "whose only coupling to the supervisor is messages "
                         "and the checkpoint directory (0 = in-process "
                         "--fabric supervisor)")
    ap.add_argument("--heartbeat-every", type=float, default=0.25,
                    help="worker heartbeat period in seconds (cross-process "
                         "fabric); liveness deadlines are multiples of this")
    ap.add_argument("--heartbeat-miss-limit", type=int, default=12,
                    help="consecutive missed heartbeat deadlines before a "
                         "worker is declared dead, reaped, and respawned")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request deadline in seconds from submission "
                         "(cross-process fabric; 0 = none): expired-while-"
                         "queued requests error without costing a launch")
    ap.add_argument("--queue-limit", type=int, default=0,
                    help="admission queue high-water mark (cross-process "
                         "fabric; 0 = unbounded): submissions past it are "
                         "rejected with a counted error result")
    args = ap.parse_args(argv)

    import os
    import tempfile

    if args.workers > 0 and os.environ.get("JAX_PLATFORMS") != "cpu":
        # the supervisor builds params in JAX and each worker is a JAX
        # process of its own; an accelerator belongs to one process, so a
        # worker would wait forever on the device the supervisor holds
        ap.error("--workers starts one JAX process per worker and runs only "
                 "with JAX_PLATFORMS=cpu; on an accelerator serve in-process "
                 "(--fabric N)")

    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    import jax

    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config, get_smoke_config
    from repro.core.plans import TreePlan
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import init_sharded_params
    from repro.parallel.sharding import param_shardings
    from repro.runtime.fabric import FabricConfig, ServeFabric
    from repro.runtime.faults import FaultInjector, parse_faults
    from repro.runtime.straggler import StragglerDetector

    tree = None
    branching = None
    spec_width = max(args.spec_tokens, 1)
    if args.draft_tree:
        branching = [int(v) for v in args.draft_tree.split(",") if v.strip()]
        tree = TreePlan.from_branching(branching).validate()
        spec_width = tree.num_nodes

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(
        cfg, decode_plane=args.decode_plane or cfg.decode_plane,
        spec_tokens=spec_width,
        paged=args.paged or cfg.paged,
        page_size=args.page_size or cfg.page_size,
        kv_dtype=args.kv_dtype or cfg.kv_dtype,
        expert_dtype=args.expert_dtype or cfg.expert_dtype,
    )
    telemetry = args.telemetry and cfg.decode_plane and cfg.is_moe
    mesh = make_host_mesh(args.data, args.model)
    B, S, T = args.slots, args.prompt_len, spec_width
    n_req = args.requests or 2 * B * args.fabric
    max_len = S + args.shared_prefix + args.gen + T

    # synthetic ragged request queue: a few distinct length buckets (S/4 to
    # S) so the per-length prefill jit cache stays small; --shared-prefix
    # prepends one common system prompt to every request so admissions
    # after the first bind its full pages straight from the prefix trie
    buckets = sorted({max(4, S // 4), max(4, S // 2), max(4, (3 * S) // 4), S})
    rng = np.random.default_rng(0)
    prog_spec = None
    if args.program:
        raw = args.program
        if raw.startswith("@"):
            with open(raw[1:]) as f:
                raw = f.read()
        prog_spec = json.loads(raw)
        compile_program(prog_spec, cfg.vocab_size)  # fail fast on a bad spec
        if program_slots(prog_spec) > B:
            ap.error(f"--program forks {program_slots(prog_spec)} ways but "
                     f"--slots is {B}")
    sys_prompt = np.asarray(
        rng.integers(0, cfg.vocab_size, size=args.shared_prefix), np.int32
    )
    requests = [
        Request(
            rid=i,
            prompt=np.concatenate([
                sys_prompt,
                np.asarray(
                    rng.integers(0, cfg.vocab_size, size=buckets[i % len(buckets)]),
                    np.int32,
                ),
            ]),
            gen=args.gen,
            program=prog_spec,
        )
        for i in range(n_req)
    ]

    # created directly under their serve shardings: a model sized to fill
    # its mesh never exists whole on one device
    params = init_sharded_params(cfg, mesh, jax.random.PRNGKey(0))
    specs = parse_faults(args.inject)
    injector = FaultInjector(specs) if specs else None

    ckpt = None
    checkpoint_every = args.checkpoint_every or (4 if specs else 0)
    tmpdir = None
    if checkpoint_every:
        ckpt_dir = args.checkpoint_dir
        if not ckpt_dir:
            tmpdir = tempfile.TemporaryDirectory(prefix="serve_fabric_ckpt_")
            ckpt_dir = tmpdir.name
        ckpt = CheckpointManager(ckpt_dir, keep=2)

    if args.workers > 0:
        code = run_cross_process(
            args, cfg, requests, params, specs, ckpt,
            spec_width=spec_width, branching=branching, max_len=max_len,
        )
        if tmpdir is not None:
            tmpdir.cleanup()
        return {"code": code}

    def restore_params(mgr):
        abs_p = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        p, _, _, _ = mgr.restore(abs_p, {}, param_shardings=param_shardings(abs_p, mesh))
        return p

    ladder = degrade_ladder(tree, T)
    make = make_replica_factory(
        cfg, mesh, B, max_len, params, ladder,
        drafter=args.drafter, telemetry=telemetry,
        fault_hook=injector.check if injector else None,
        launch_timeout=args.launch_timeout, ckpt=ckpt,
        shrink_to=(max(args.model, len(jax.devices()) // 2), args.model),
    )
    fabric = ServeFabric(
        make, requests,
        FabricConfig(
            n_replicas=args.fabric,
            launch_timeout=args.launch_timeout,
            checkpoint_every=checkpoint_every,
            max_degrade_level=len(ladder) - 1,
            synthetic_step_times=bool(specs),
        ),
        ckpt=ckpt,
        restore_params=restore_params if ckpt else None,
        params=params,
        detector=StragglerDetector(n_workers=args.fabric, warmup=8) if args.fabric > 1 else None,
    )
    t_start = time.perf_counter()
    results = fabric.run()
    wall = time.perf_counter() - t_start
    _dump_tokens(args, results)
    if tmpdir is not None:
        tmpdir.cleanup()

    st = fabric.stats
    generated = st["accepted"]
    finished = sum(1 for r in results.values() if r.error is None)
    print(f"served {finished} requests on {args.fabric}x{B} slots: {generated} "
          f"tokens in {wall*1e3:.1f} ms ({generated/max(wall, 1e-9):.0f} tok/s, "
          f"{st['launches']} launches, prefill {st['prefill_ms']:.1f} ms total)")
    if T > 1:
        shape = f"tree {args.draft_tree}" if tree is not None else f"width {T}"
        print(f"speculative: {shape} ({T} nodes), drafter {args.drafter}, "
              f"accept rate {st['accepted']/max(st['drafted'], 1):.2f} "
              f"({st['accepted']/max(st['launches'], 1):.2f} tokens/launch)")
    if args.paged or cfg.paged:
        adm = st["paged_admissions"]
        print(f"paged: {adm} admissions, {st['pages_shared']} pages bound via "
              f"prefix trie ({st['pages_shared']/max(adm, 1):.2f}/admission), "
              f"{st['admit_copy_rows']} KV rows copied at admission")
    if telemetry and st["agreements"]:
        print(f"plan telemetry: stale-vs-fresh top-k agreement "
              f"mean {np.mean(st['agreements']):.3f} min {np.min(st['agreements']):.3f} "
              f"over {len(st['agreements'])} launches")
    if args.program:
        frac = st["prog_mask_frac_sum"] / max(st["prog_mask_cnt"], 1)
        print(f"programs: {st['prog_tokens']} constrained tokens, "
              f"{st['prog_states_visited']} automaton states visited, "
              f"masked-token fraction {frac:.3f}, {st['forks_started']} forks "
              f"(live max {st['forks_live_max']}, {st['fork_kv_rows_copied']} "
              f"KV rows copied at fork), {st['prog_masked_emissions']} "
              f"masked emissions")
    if args.fabric > 1 or specs:
        print(f"fabric: {st['crashes']} crashes, {st['rejoins']} rejoins "
              f"({st['rewarm_prefills']} re-warm prefills, {st['restores']} "
              f"checkpoint restores), {st['transient_failures']} transient "
              f"failures ({st['timeouts']} timeouts, {st['backoff_rounds']} "
              f"backoff rounds), {st['poisoned']} poisoned, "
              f"{len(st['degradations'])} degradations, {st['excluded']} "
              f"excluded, {st['dropped']} dropped, {st['duplicates']} duplicates")

    unanswered = [r.rid for r in requests if r.rid not in results]
    poison_expected = any(s.kind == "poison" for s in specs)
    errors = [r for r in results.values() if r.error is not None]
    code = 0
    if unanswered:
        print(f"FABRIC ERROR: {len(unanswered)} requests unanswered: {unanswered}")
        code = 1
    if errors and not poison_expected:
        print(f"FABRIC ERROR: {len(errors)} requests errored without poison "
              f"injection: {[(r.rid, r.error) for r in errors]}")
        code = 1
    if st["duplicates"]:
        print(f"FABRIC ERROR: {st['duplicates']} duplicate results published")
        code = 1
    if args.expect_shared_pages and st["pages_shared"] == 0:
        print("FABRIC ERROR: --expect-shared-pages set but no page was shared "
              "across admissions")
        code = 1
    if st["prog_masked_emissions"]:
        print(f"FABRIC ERROR: {st['prog_masked_emissions']} tokens emitted "
              f"outside their automaton's allowed set")
        code = 1
    return {
        "code": code, "requests": len(requests), "served": finished,
        "tokens": generated, "wall_s": wall, "stats": st,
    }


if __name__ == "__main__":
    raise SystemExit(main()["code"])
