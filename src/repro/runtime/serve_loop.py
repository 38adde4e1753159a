"""Continuous-batching coded serving: Poisson admission, per-step coded
rounds, pow2 slot bucketing.

The PR 5 serve loop was static batching: admit a fixed batch, run it to
completion, repeat — late arrivals wait for the whole previous batch and
early finishers hold their slots as dead weight.  This loop is the
standard continuous-batching scheduler on top of the coded round
machinery:

* **admission** — requests arrive on a (virtual-clock) Poisson timeline;
  any free slot admits the next arrival at the step boundary;
* **eviction** — a request leaves its slot the step it hits its ``gen``
  budget or emits EOS; survivors are compacted to the front;
* **bucketing** — the jitted step only ever sees pow2 batch widths
  (active slots padded up to the bucket), so admission/eviction churn
  re-dispatches an already-compiled program instead of retracing —
  ``trace_count`` is asserted flat in the tests;
* **one coded round per step** — on the virtual transport every selected
  projection of every in-flight request runs inside ONE jitted step
  program (``models.coded.build_coded_logits``) under ONE straggler plan
  and ONE decode mask per step, the spec's wait policy choosing the
  responder prefix.

Prefill rides the decode path: an admitted request is teacher-forced one
prompt token per step (its slot's ``pos`` trails the others), so a step
is always "one token for every in-flight slot" — no separate prefill
program, no bucket-shape churn from ragged prompts.

Timing splits two clocks: the **virtual clock** (straggler waits + the
master's measured per-step wall) prices throughput/latency the way every
other round does; **busy wall** sums only the measured master dispatches,
so ``tok_s`` excludes admission idle by construction.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from .spans import span

__all__ = ["Request", "ServedRequest", "ServeResult", "poisson_workload",
           "ContinuousBatcher", "logit_gap"]


@dataclasses.dataclass
class Request:
    """One serving request: a prompt and a generation budget."""
    rid: int
    prompt: np.ndarray               # (L,) int32 token ids, L >= 1
    gen: int                         # tokens to generate
    arrival_s: float = 0.0           # virtual arrival time


@dataclasses.dataclass
class ServedRequest:
    """One finished request with its timeline on the virtual clock."""
    rid: int
    arrival_s: float
    admitted_s: float
    first_token_s: float             # virtual time the first token decoded
    done_s: float
    n_prompt: int
    tokens: np.ndarray               # (gen'd,) int32
    # (n_prompt - 1 + gen'd, V) f32 logits the slot read at each of its
    # steps (``run(record_logits=True)``): row j follows feeding the j-th
    # token of ``prompt + tokens[:-1]``
    logits: Optional[np.ndarray] = None

    @property
    def ttft_s(self) -> float:
        """Time to first token, measured from ARRIVAL (queueing included —
        this is what an admission policy is judged on)."""
        return self.first_token_s - self.arrival_s


@dataclasses.dataclass
class ServeResult:
    """One serve run: finished requests + per-step accounting."""
    requests: List[ServedRequest]
    step_stats: list                 # one RoundStats per step
    step_virtual_s: np.ndarray       # (n_steps,) virtual duration per step
    buckets: np.ndarray              # (n_steps,) jitted batch width per step
    busy_wall_s: float               # Σ measured master dispatch wall
    virtual_s: float                 # virtual makespan (last eviction)
    trace_count: int                 # step-program traces (compile events)
    mode: str                        # "instep" | "round" | "plain"
    coded_fraction: float            # analytic coded share of step FLOPs
    # (n_steps,) host perf_counter seconds from the pass's start to the
    # moment each step's outputs were ready (its timed dispatch's end)
    step_end_wall_s: np.ndarray

    @property
    def n_steps(self) -> int:
        return len(self.step_virtual_s)

    @property
    def ttft_s(self) -> np.ndarray:
        return np.asarray([r.ttft_s for r in self.requests])

    @property
    def p50_step_s(self) -> float:
        return float(np.percentile(self.step_virtual_s, 50)) \
            if self.n_steps else 0.0

    @property
    def p99_step_s(self) -> float:
        return float(np.percentile(self.step_virtual_s, 99)) \
            if self.n_steps else 0.0

    @property
    def requests_per_s(self) -> float:
        """Served requests over the virtual makespan — the end-to-end
        serving throughput the admission policy is gated on."""
        return len(self.requests) / max(self.virtual_s, 1e-12)

    @property
    def generated(self) -> int:
        return sum(len(r.tokens) for r in self.requests)

    @property
    def tok_s(self) -> float:
        """Decode throughput over BUSY wall only — admission idle (the
        loop parked waiting for the next Poisson arrival) is excluded."""
        return self.generated / max(self.busy_wall_s, 1e-12)


def poisson_workload(n_requests: int, *, rate_rps: float, prompt_len: int,
                     gen: int, vocab: int, seed: int = 0,
                     ragged: bool = True) -> List[Request]:
    """A Poisson arrival trace of random-token requests.

    Inter-arrival gaps are exponential at ``rate_rps`` (0 = everything
    arrives at t=0); ``ragged`` draws per-request prompt lengths in
    [max(2, prompt_len/2), prompt_len] AND generation budgets in
    [max(1, gen/4), gen] instead of uniform shapes — the regime where
    static batching bleeds slots on early finishers.
    """
    rng = np.random.default_rng(seed)
    if rate_rps > 0:
        arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, n_requests))
        arrivals -= arrivals[0]                     # first request at t=0
    else:
        arrivals = np.zeros(n_requests)
    reqs = []
    for i in range(n_requests):
        plen, g = prompt_len, gen
        if ragged:
            plen = int(rng.integers(max(2, prompt_len // 2), prompt_len + 1))
            g = int(rng.integers(max(1, gen // 4), gen + 1))
        prompt = rng.integers(1, vocab, plen).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, gen=g,
                            arrival_s=float(arrivals[i])))
    return reqs


@dataclasses.dataclass
class _Slot:
    req: Request
    admitted_s: float
    fed: int = 0                     # prompt tokens already in the cache
    last_tok: int = 0
    first_token_s: float = float("nan")
    tokens: List[int] = dataclasses.field(default_factory=list)
    logits: list = dataclasses.field(default_factory=list)
    done: bool = False               # gated mode: finished but slot-bound


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class ContinuousBatcher:
    """The continuous-batching serve loop over one engine + model.

    ``mode`` resolution:

    * ``coded_layers="none"`` → **plain**: the unmodified decode step,
      still continuously batched (the uncoded baseline);
    * virtual transport + a fused-capable scheme → **instep**: the whole
      step (all selected coded sites) is one jitted dispatch
      (``build_coded_logits``), priced by one straggler plan per step;
    * real transports (threads/socket) → **round**: the PR 5 semantics —
      hidden state on the master, the unembed projection as one real
      ``engine.matmul`` round per step (spec validation already restricts
      real transports to ``coded_layers="unembed"``).

    ``admission="gated"`` reproduces the PR 5 static-batch scheduler
    (admit only into an EMPTY machine, hold finished requests in their
    slots until the whole batch drains) — the baseline the continuous
    policy is benchmarked against with everything else held equal.
    """

    def __init__(self, engine, model, params, *, coded_layers: str = "unembed",
                 max_slots: int = 8, eos_id: Optional[int] = None,
                 backend: str = "virtual", admission: str = "continuous",
                 round0: int = 0):
        import jax
        import jax.numpy as jnp
        self._jax, self._jnp = jax, jnp
        if admission not in ("continuous", "gated"):
            raise ValueError(f"unknown admission policy {admission!r}")
        self.engine = engine
        self.model = model
        self.params = params
        self.coded_layers = coded_layers
        self.max_slots = int(max_slots)
        self.eos_id = eos_id
        self.admission = admission
        self._round = round0
        self.trace_count = 0

        supports_fused = bool(getattr(engine.scheme, "supports_fused", False))
        if coded_layers == "none":
            self.mode = "plain"
        elif backend == "virtual" and supports_fused:
            self.mode = "instep"
        elif coded_layers == "unembed":
            self.mode = "round"
        else:
            raise ValueError(
                f"coded_layers={coded_layers!r} needs the in-step coded path "
                f"(virtual transport + a fused-capable scheme); "
                f"backend={backend!r} supports_fused={supports_fused}")

        cfg = model.cfg
        # ``_forward`` is the step program up to what the host reads: the
        # (B, V) f32 logits, or — round mode — the (B, d) f32 hidden state
        # whose unembed is the real coded round, with the new cache and the
        # rows' routing choices on held experts (None without MoE layers).
        # The jitted ``_step`` adds the greedy argmax and returns the
        # logits it read alongside; a model that routes returns its counts
        # stacked under the tokens, so one copy brings both to the host.
        if self.mode == "instep":
            from ..models.coded import (build_coded_logits,
                                        coded_flop_fraction,
                                        encode_serving_weights)
            self.code = encode_serving_weights(engine.scheme, model, params,
                                               coded_layers)
            self.wire_params = engine.serve_wire_params()
            self._forward = build_coded_logits(
                model, engine.scheme, self.code, wire_params=self.wire_params)
            self.coded_fraction = coded_flop_fraction(cfg, coded_layers)
            self._t_comp: Dict[int, float] = {}
        elif self.mode == "round":
            from ..models.coded import coded_flop_fraction

            def hidden(params, cache, tokens, pos):
                h, nc = model.decode_step(params, cache, tokens, pos,
                                          return_hidden=True)
                return h[:, 0, :].astype(jnp.float32), nc, None

            self._forward = hidden
            emb = params["embedding"]
            self._wt = np.asarray(emb["table"] if cfg.tie_embeddings
                                  else emb["unembed"].T, np.float32)
            self.coded_fraction = coded_flop_fraction(cfg, "unembed")
        else:

            def plain(params, cache, tokens, pos):
                logits, nc = model.decode_step(params, cache, tokens, pos)
                return logits[:, 0, :].astype(jnp.float32), nc, None

            self._forward = plain
            self.coded_fraction = 0.0

        def step(*args):
            self.trace_count += 1            # runs at trace time only
            with span("trace", fn="serve_step"):
                out, nc, here = self._forward(*args)
                if self.mode == "round":
                    return out, nc
                nxt = jnp.argmax(out, axis=-1).astype(jnp.int32)
                if here is not None:
                    nxt = jnp.stack([nxt, here])
                return nxt, out, nc

        self._step = jax.jit(step)
        self._warm: set = set()              # buckets already compiled

    # ---------------------------------------------------------- cache ops
    def _slice_cache(self, cache, b):
        """The leading-``b``-slots view the bucketed step runs on
        (prelude leaves batch on axis 0, group leaves on axis 1)."""
        with span("serve.cache"):
            return {"prelude": self._jax.tree.map(lambda a: a[:b],
                                                  cache["prelude"]),
                    "groups": self._jax.tree.map(lambda a: a[:, :b],
                                                 cache["groups"])}

    def _merge_cache(self, cache, new, b):
        with span("serve.cache"):
            return {"prelude": self._jax.tree.map(
                        lambda full, nw: full.at[:b].set(nw),
                        cache["prelude"], new["prelude"]),
                    "groups": self._jax.tree.map(
                        lambda full, nw: full.at[:, :b].set(nw),
                        cache["groups"], new["groups"])}

    def _gather_cache(self, cache, perm):
        """Slot compaction after evictions: row ``i`` ← old row
        ``perm[i]``."""
        with span("serve.cache"):
            idx = self._jnp.asarray(perm, self._jnp.int32)
            return {"prelude": self._jax.tree.map(lambda a: a[idx],
                                                  cache["prelude"]),
                    "groups": self._jax.tree.map(lambda a: a[:, idx],
                                                 cache["groups"])}

    def _zero_slot(self, cache, i):
        """Admission reset.  KV reads are position-masked so stale keys
        are unreachable, but SSM conv/recurrent state is NOT — a freshly
        admitted request must start from zeros."""
        z = lambda a: a.at[i].set(self._jnp.zeros_like(a[i]))
        zg = lambda a: a.at[:, i].set(self._jnp.zeros_like(a[:, i]))
        with span("serve.cache"):
            return {"prelude": self._jax.tree.map(z, cache["prelude"]),
                    "groups": self._jax.tree.map(zg, cache["groups"])}

    # ----------------------------------------------------------- stepping
    def _site_t_comp(self, b: int) -> float:
        """Per-worker virtual compute of one step at bucket ``b`` — each
        worker runs every coded site's shard back-to-back."""
        if b not in self._t_comp:
            self._t_comp[b] = sum(
                self.engine.worker_time(l, r)
                for l, r in self.code.site_shapes(b))
        return self._t_comp[b]

    def _timed(self, b, *args):
        """Dispatch the step at bucket ``b``, returning (out, wall_s,
        end) with compile excluded: the first call at a new bucket
        compiles and runs, then an identical (pure) call is timed; ``end``
        is the host clock when its outputs were ready."""
        jax = self._jax
        if b not in self._warm:
            with span("serve.dispatch", new_bucket=1):
                out = self._step(*args)
            with span("serve.wait"):
                jax.block_until_ready(out)
            self._warm.add(b)
        t0 = time.perf_counter()
        with span("serve.dispatch", new_bucket=0):
            out = self._step(*args)
        with span("serve.wait"):
            jax.block_until_ready(out)
        end = time.perf_counter()
        return out, end - t0, end

    def _run_step(self, cache, tok, pos, b):
        """One step at bucket ``b``: returns (next_tokens (b,), logits
        (b, V) — on the device, except in round mode — new cache,
        RoundStats, virtual_dur_s, wall_s, end, here): ``end`` is the host
        clock when the step's outputs were ready; ``here`` (b,) each row's
        routing choices on held experts, or None."""
        jnp = self._jnp
        from .engine import RoundStats
        sliced = self._slice_cache(cache, b)
        plan, mats, crypto = None, {}, 0.0
        if self.mode == "instep":
            with span("serve.plan"):
                plan = self.engine.serve_round_plan(self._round,
                                                   self._site_t_comp(b))
                self._round += 1
                if self.wire_params is not None:
                    mats = self.code.step_materials(self.engine)
                    crypto = self.engine.serve_crypto_time(
                        *self.code.wire_elems(b))
        with span("serve.inputs"):
            tok_a = jnp.asarray(tok[:b, None], jnp.int32)
            pos_a = jnp.asarray(pos[:b], jnp.int32)
            mask = None if plan is None else jnp.asarray(plan.mask)
        if self.mode == "instep":
            (nxt, logits, new_cache), wall, end = self._timed(
                b, self.params, sliced, tok_a, pos_a, mask,
                self.code.arrays, mats)
            self.engine.dispatch_count += 1
            stats = self.engine._stats(
                plan.events, plan.wait_s, encode_s=wall,
                compute_wait_s=plan.wait_s, decode_s=0.0, crypto_s=crypto,
                n_waited=len(plan.responders), dispatches=1)
            virt = stats.total_s
        elif self.mode == "round":
            (h, new_cache), wall, _ = self._timed(b, self.params, sliced,
                                                  tok_a, pos_a)
            t0 = time.perf_counter()
            with span("serve.to_host"):
                h = np.asarray(h)
            prod, stats = self.engine.matmul(self._wt, h.T,
                                             round_idx=self._round)
            end = time.perf_counter()
            wall += end - t0
            self._round += 1
            logits = np.asarray(prod).T
            nxt = logits.argmax(-1).astype(np.int32)
            virt = stats.total_s
        else:
            (nxt, logits, new_cache), wall, end = self._timed(
                b, self.params, sliced, tok_a, pos_a)
            stats = RoundStats(encode_s=wall, compute_wait_s=0.0,
                               decode_s=0.0, policy="uncoded", dispatches=1)
            virt = wall
        cache = self._merge_cache(cache, new_cache, b)
        with span("serve.to_host"):
            nxt = np.asarray(nxt)
        here = None
        if nxt.ndim == 2:
            nxt, here = nxt
        return nxt, logits, cache, stats, virt, wall, end, here

    # --------------------------------------------------------------- loop
    def run(self, requests: Sequence[Request], *,
            record_logits: bool = False,
            on_step: Optional[Callable[[int], None]] = None) -> ServeResult:
        """Serve ``requests`` to completion.  ``record_logits`` copies
        each step's logits to the host and returns every request's rows
        in :attr:`ServedRequest.logits` — what parity checks compare,
        since greedy tokens flip on near-ties.  ``on_step(i)`` runs on the
        host once before step ``i`` (from 0), after its admissions: a
        profiler started and stopped there holds whole steps."""
        t_pass = time.perf_counter()
        reqs = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        max_len = max(len(r.prompt) + r.gen for r in reqs) + 1
        cache = self.model.init_cache(self.max_slots, max_len)
        pending = deque(reqs)
        slots: List[_Slot] = []
        served: List[ServedRequest] = []
        step_stats, virt_log, bucket_log, end_log = [], [], [], []
        t_v = 0.0
        busy = 0.0
        tok = np.zeros(self.max_slots, np.int32)
        pos = np.zeros(self.max_slots, np.int32)

        while pending or slots:
            # ---- admission at the step boundary.  Continuous: any free
            # slot takes the next arrival.  Gated (the PR 5 static-batch
            # baseline): only an EMPTY machine admits, so late arrivals
            # wait out the whole in-flight batch.
            if self.admission != "gated" or not slots:
                while (pending and len(slots) < self.max_slots
                       and pending[0].arrival_s <= t_v + 1e-12):
                    r = pending.popleft()
                    if r.gen <= 0:               # nothing to decode
                        served.append(ServedRequest(
                            rid=r.rid, arrival_s=r.arrival_s, admitted_s=t_v,
                            first_token_s=t_v, done_s=t_v,
                            n_prompt=len(r.prompt),
                            tokens=np.zeros(0, np.int32)))
                        continue
                    with span("serve.admit"):
                        cache = self._zero_slot(cache, len(slots))
                        slots.append(_Slot(req=r, admitted_s=t_v))
            if not slots:
                if not pending:                  # everything drained
                    break
                t_v = max(t_v, pending[0].arrival_s)   # idle: jump ahead
                continue

            # ---- the bucketed step: ``live`` of its ``b`` rows serve a
            # request (a gated batch's finished ones hold theirs idle);
            # a model that routes adds ``routed_here``, the live rows'
            # routing choices that fell on held experts
            if on_step is not None:
                on_step(len(step_stats))
            b = _next_pow2(len(slots))
            live = sum(not s.done for s in slots)
            with span("serve.step", bucket=b, live=live) as step_span:
                for i, s in enumerate(slots):
                    plen = len(s.req.prompt)
                    tok[i] = (s.req.prompt[s.fed] if s.fed < plen
                              else s.last_tok)
                    pos[i] = s.fed
                tok[len(slots):b] = 0            # padded slots: ignored rows
                pos[len(slots):b] = 0
                (nxt, logits, cache, stats, virt, wall, end,
                 here) = self._run_step(cache, tok, pos, b)
                if here is not None:
                    step_span.set_metadata(routed_here=int(sum(
                        here[i] for i, s in enumerate(slots) if not s.done)))
                if record_logits:
                    with span("serve.to_host"):
                        logits = np.asarray(logits, np.float32)
                    for i, s in enumerate(slots):
                        if not s.done:
                            s.logits.append(logits[i])
                busy += wall
                t_v += virt
                step_stats.append(stats)
                virt_log.append(virt)
                bucket_log.append(b)
                end_log.append(end - t_pass)

                # ---- consume outputs, evict finishers
                with span("serve.consume"):
                    finished: List[int] = []
                    for i, s in enumerate(slots):
                        if s.done:
                            continue
                        plen = len(s.req.prompt)
                        if s.fed >= plen - 1:    # argmax is a generated token
                            t = int(nxt[i])
                            s.tokens.append(t)
                            s.last_tok = t
                            if len(s.tokens) == 1:
                                s.first_token_s = t_v
                            if (len(s.tokens) >= s.req.gen
                                    or (self.eos_id is not None
                                        and t == self.eos_id)):
                                s.done = True
                                served.append(ServedRequest(
                                    rid=s.req.rid, arrival_s=s.req.arrival_s,
                                    admitted_s=s.admitted_s,
                                    first_token_s=s.first_token_s, done_s=t_v,
                                    n_prompt=plen,
                                    tokens=np.asarray(s.tokens, np.int32),
                                    logits=(np.stack(s.logits)
                                            if record_logits else None)))
                                finished.append(i)
                        s.fed += 1
                    if self.admission == "gated":
                        # finished requests hold their slots until the
                        # batch drains
                        if all(s.done for s in slots):
                            slots = []
                    elif finished:
                        keep = [i for i in range(len(slots))
                                if i not in finished]
                        perm = keep + [i for i in range(self.max_slots)
                                       if i not in keep]
                        cache = self._gather_cache(cache,
                                                   perm[:self.max_slots])
                        slots = [slots[i] for i in keep]

        served.sort(key=lambda r: r.rid)
        return ServeResult(
            requests=served, step_stats=step_stats,
            step_virtual_s=np.asarray(virt_log),
            buckets=np.asarray(bucket_log, np.int64), busy_wall_s=busy,
            virtual_s=t_v, trace_count=self.trace_count, mode=self.mode,
            coded_fraction=self.coded_fraction,
            step_end_wall_s=np.asarray(end_log))


def logit_gap(served: Sequence[ServedRequest],
              reference: Sequence[ServedRequest]) -> Dict[str, float]:
    """Compare the recorded logits of two serves of one workload (both
    run with ``record_logits=True``), request by request.

    A row is compared while both serves fed the request the same tokens:
    the whole prompt, then generated tokens up to and including the step
    that produced the first token they disagree on (after it the two
    continuations are different inputs).  Returns the largest absolute
    logit difference (``max_abs_diff``), the largest absolute reference
    logit over the compared rows (``scale``), the rows compared and the
    rows recorded (``rows``, ``rows_total``) and the share of generated
    tokens that agree (``token_agreement``)."""
    err = scale = 0.0
    rows = rows_total = match = total = 0
    for a, b in zip(served, reference):
        if a.rid != b.rid:
            raise ValueError(f"request {a.rid} compared with {b.rid}")
        n = min(len(a.tokens), len(b.tokens))
        differ = np.flatnonzero(a.tokens[:n] != b.tokens[:n])
        k = a.n_prompt - 1 + (int(differ[0]) + 1 if differ.size else n)
        if k:
            err = max(err, float(np.abs(a.logits[:k] - b.logits[:k]).max()))
            scale = max(scale, float(np.abs(b.logits[:k]).max()))
        rows += k
        rows_total += len(a.logits)
        match += int(np.sum(a.tokens[:n] == b.tokens[:n]))
        total += max(len(a.tokens), len(b.tokens))
    return {"max_abs_diff": err, "scale": scale, "rows": rows,
            "rows_total": rows_total,
            "token_agreement": match / max(total, 1)}
