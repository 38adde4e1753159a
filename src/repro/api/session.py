"""``Session``: the context-managed runtime behind one ``ClusterSpec``.

One typed entry point for every workload the stack runs:

    with Session(ClusterSpec.serve_deadline(t_budget=0.005)) as s:
        out, stats = s.matmul(a, b)            # one coded round
        curve = s.anytime_curve(a, b)          # error-vs-latency curve
        s.init_mlp((784, 64, 10), lr=0.1)
        loss, elapsed = s.train_step(x, y)     # SPACDC-DL (Algorithm 2)
        report = s.serve(arch="qwen2-7b")      # coded deadline serving

The Session owns the pool/executor lifecycle: the long-lived thread
executor behind the ``"threads"`` transport is torn down exactly once on
``close()`` / context exit, and repeated open/close cycles never leak
threads (asserted in tests).  The legacy ``DistributedMatmul`` /
``CodedMaster`` constructors are thin shims over the same
``runtime.engine.RoundEngine`` this Session drives, so both surfaces
produce bit-identical rounds.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..runtime.engine import RoundEngine, RoundStats
from .spec import ClusterSpec

__all__ = ["Session", "ServeReport", "coded_mlp_init", "coded_mlp_step"]


# --------------------------------------------------------------------------
# the SPACDC-DL training step (Algorithm 2), functional form
# --------------------------------------------------------------------------

def coded_mlp_init(layer_sizes: Sequence[int], seed: int = 0):
    """He-initialized MLP state: (weights, biases) — the exact layer init
    the SPACDC-DL master has always used (bit-identical)."""
    rng = np.random.default_rng(seed)
    weights = [rng.standard_normal((m, n)).astype(np.float32) *
               np.sqrt(2.0 / m)
               for m, n in zip(layer_sizes[:-1], layer_sizes[1:])]
    biases = [np.zeros(n, np.float32) for n in layer_sizes[1:]]
    return weights, biases


def _act(x):
    return np.maximum(x, 0.0)


def _act_grad(x):
    return (x > 0).astype(np.float32)


def mlp_forward(weights, biases, x):
    """ReLU MLP forward: returns (activations, pre-activations)."""
    acts, pre = [x], []
    h = x
    for i, (w, b) in enumerate(zip(weights, biases)):
        z = h @ w + b
        pre.append(z)
        h = _act(z) if i < len(weights) - 1 else z
        acts.append(h)
    return acts, pre


def coded_mlp_step(weights, biases, matmul, x, y, lr: float = 0.05,
                   round0: int = 0):
    """One SGD step of SPACDC-DL (paper Algorithm 2), backward layer
    products distributed through ``matmul(a, b, round_idx) ->
    (product, RoundStats)`` — the coded job is Eq. 23's delta @ W^T,
    coded over W's rows.

    Mutates ``weights``/``biases`` in place (the master owns its state).
    Returns (loss, elapsed_virtual_s, per_round_stats).
    """
    bsz = x.shape[0]
    acts, pre = mlp_forward(weights, biases, x)
    logits = acts[-1]
    z = logits - logits.max(1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(1, keepdims=True)
    loss = -np.mean(np.log(p[np.arange(bsz), y] + 1e-12))
    onehot = np.zeros_like(p)
    onehot[np.arange(bsz), y] = 1.0
    delta = (p - onehot) / bsz                      # (B, n_out)

    elapsed = 0.0
    stats_out: List[RoundStats] = []
    grads_w, grads_b = [], []
    for l in reversed(range(len(weights))):
        grads_w.append(acts[l].T @ delta)
        grads_b.append(delta.sum(0))
        if l > 0:
            # the distributed job (Eq. 23): delta @ W^T, coded over W rows
            prod, stats = matmul(weights[l], delta.T,
                                 round_idx=round0 + len(stats_out))
            delta = prod.T * _act_grad(pre[l - 1])
            elapsed += stats.total_s
            stats_out.append(stats)
    grads_w, grads_b = grads_w[::-1], grads_b[::-1]
    for i in range(len(weights)):
        weights[i] -= lr * grads_w[i]
        biases[i] -= lr * grads_b[i]
    return float(loss), elapsed, stats_out


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ServeReport:
    """One coded serving run: what came out and what every step cost.

    The continuous-batching loop (``runtime.serve_loop``) serves requests
    off a (possibly Poisson) arrival timeline, so the report carries two
    clocks: the **virtual clock** (straggler waits + measured master
    walls — ``virtual_s``, ``step_latency_s``, per-request timelines) and
    **busy wall** (measured master dispatches only).  ``tok_s`` divides
    by busy wall, so admission idle — the loop parked waiting for the
    next arrival — never inflates decode throughput.
    """
    tokens: np.ndarray               # (n_requests, max_gen) ids, -1 padded
    step_stats: List[RoundStats]     # ONE coded round per decode step
    wall_s: float                    # busy wall of the serve loop
    tok_s: float                     # generated tokens / busy wall
    t_budget: Optional[float]        # the Deadline budget (None: no deadline)
    argmax_agreement: float          # fraction of coded tokens == uncoded
    # --- continuous-batching accounting ----------------------------------
    requests: list = dataclasses.field(default_factory=list)
    ttft_s: np.ndarray = dataclasses.field(           # per-request TTFT
        default_factory=lambda: np.zeros(0))          # (arrival → 1st token)
    step_latency_s: np.ndarray = dataclasses.field(   # per-step virtual
        default_factory=lambda: np.zeros(0))          # durations
    p50_step_s: float = 0.0
    p99_step_s: float = 0.0
    requests_per_s: float = 0.0      # served requests / virtual makespan
    virtual_s: float = 0.0           # virtual makespan of the run
    busy_wall_s: float = 0.0
    coded_fraction: float = 0.0      # analytic coded share of step FLOPs
    trace_count: int = 0             # step-program compiles (churn-free: a
                                     # few pow2 buckets, however slots churn)
    mode: str = ""                   # "instep" | "round" | "plain"

    @property
    def steps_within_budget(self) -> int:
        """Decode steps whose coded decode fired at/before the deadline
        (all of them, for a rateless scheme — SPACDC's minimum decodable
        prefix is 1)."""
        if self.t_budget is None:
            return len(self.step_stats)
        return sum(1 for s in self.step_stats
                   if s.decode_at_s <= self.t_budget + 1e-12)


class Session:
    """Context-managed front door over the whole SPACDC stack.

    Everything is configured by the frozen :class:`~repro.api.ClusterSpec`
    — scheme, privacy, crypto, wait policy, straggler environment,
    transport backend.  ``straggler`` / ``policy`` accept pre-built
    instances for the legacy shims (objects a spec can't express).
    """

    def __init__(self, spec: ClusterSpec, *, straggler=None, policy=None):
        self.spec = spec
        self.engine = RoundEngine(spec, straggler=straggler, policy=policy)
        self._closed = False
        self._mlp = None                 # (weights, biases, lr)
        self._round = 0
        self.round_stats: List[RoundStats] = []
        self._serve_models: dict = {}    # (arch|cfg, tiny, seed) -> model,
                                         # params
        self._serve_batchers: dict = {}  # + (coded_layers, admission) ->
                                         # ContinuousBatcher (compiled steps,
                                         # pre-encoded weights, warm buckets)

    # ----------------------------------------------------------- lifecycle
    def __enter__(self) -> "Session":
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False

    def close(self):
        """Tear down the pool's long-lived executor — exactly once; later
        calls are no-ops.  Unconsumed-straggler failures surface here."""
        if not self._closed:
            self._closed = True
            self.engine.close()
            # drop served models and their pre-encoded shards: device
            # memory goes back as soon as the caller drops the session
            self._serve_models.clear()
            self._serve_batchers.clear()

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def health(self):
        """The engine's :class:`~repro.runtime.faults.WorkerHealth`
        tracker (None unless the spec's ``FaultSpec`` is active or
        ``AdaptiveSpec`` is enabled) — EWMA latency, crash/drop/corrupt
        counts, quarantine state per worker."""
        return self.engine.health

    def adaptive_report(self) -> dict:
        """JSON-ready snapshot of the adaptive controller's state: the
        fitted straggler model, the candidate space, every per-round
        :class:`~repro.runtime.adaptive.Decision`, and the per-worker
        health (``WorkerHealth.to_dict``).  With ``policy="fixed"`` the
        report just says so — callers (``launch/serve.py --report``) can
        dump it unconditionally."""
        eng = self.engine
        report = {
            "scheme": self.spec.code.scheme,
            "n_workers": self.spec.code.n_workers,
            "adaptive": getattr(self.spec, "adaptive", None) is not None
            and self.spec.adaptive.enabled,
            "rounds_run": len(self.round_stats),
        }
        if eng.adaptive is not None:
            report.update(eng.adaptive.report())
            report["active"] = {
                "k_blocks": int(getattr(eng.scheme, "k_blocks", eng.k)),
                "policy": eng.policy.name,
                "fh_degree": int(eng.fh_degree),
            }
        else:
            report["policy"] = "fixed"
        if eng.health is not None:
            report["health"] = eng.health.to_dict()
        return report

    def _check_open(self):
        if self._closed:
            raise RuntimeError("Session is closed")

    # -------------------------------------------------------------- rounds
    def matmul(self, a, b, round_idx: Optional[int] = None
               ) -> Tuple[np.ndarray, RoundStats]:
        """One coded A@B round under the spec's scheme/policy/transport.
        ``round_idx`` defaults to an internal counter (each call is a new
        straggler draw); pass it explicitly to replay rounds."""
        self._check_open()
        if round_idx is None:
            round_idx = self._round
            self._round += 1
        out, stats = self.engine.matmul(a, b, round_idx=round_idx)
        self.round_stats.append(stats)
        return out, stats

    def anytime_curve(self, a, b, round_idx: int = 0):
        """Error-vs-latency curve of one round (2 jitted dispatches);
        see :meth:`repro.runtime.engine.RoundEngine.anytime_curve`."""
        self._check_open()
        return self.engine.anytime_curve(a, b, round_idx=round_idx)

    # ------------------------------------------------------------ training
    def init_mlp(self, layer_sizes: Sequence[int], lr: float = 0.05,
                 seed: int = 0) -> "Session":
        """Initialize the SPACDC-DL training state ``train_step`` advances."""
        self._check_open()
        w, b = coded_mlp_init(layer_sizes, seed)
        self._mlp = (w, b, lr)
        return self

    @property
    def mlp_weights(self):
        return self._mlp[0] if self._mlp else None

    @property
    def mlp_biases(self):
        return self._mlp[1] if self._mlp else None

    def train_step(self, x, y) -> Tuple[float, float]:
        """One coded SGD step (Algorithm 2); backward layer products run
        as coded rounds under the session's policy.  Returns
        (loss, virtual_elapsed_s); per-round stats land in
        ``round_stats``."""
        self._check_open()
        if self._mlp is None:
            raise RuntimeError("call init_mlp(layer_sizes) first")
        w, b, lr = self._mlp
        loss, elapsed, stats = coded_mlp_step(
            w, b, self.engine.matmul, x, y, lr=lr, round0=self._round)
        self._round += len(stats)
        self.round_stats.extend(stats)
        return loss, elapsed

    def mlp_accuracy(self, x, y) -> float:
        self._check_open()
        if self._mlp is None:
            raise RuntimeError("call init_mlp(layer_sizes) first")
        acts, _ = mlp_forward(self._mlp[0], self._mlp[1], x)
        return float((acts[-1].argmax(1) == y).mean())

    # ------------------------------------------------------------- serving
    def _serve_model(self, arch, tiny: bool, seed: int):
        """(model, params, cache key) for an arch name (its tiny config
        when ``tiny``) or a ``ModelConfig`` used as given — e.g. one cut
        to a chip's share of a published model."""
        import jax
        from ..configs import get_config, tiny_config
        from ..models import build_model
        if isinstance(arch, str):
            mkey = (arch, tiny, seed)
            cfg = tiny_config(arch) if tiny else get_config(arch)
        else:
            mkey = (arch, False, seed)
            cfg = arch
        if mkey not in self._serve_models:
            model = build_model(cfg)
            self._serve_models[mkey] = (model,
                                        model.init(jax.random.PRNGKey(seed)))
        return self._serve_models[mkey] + (mkey,)

    def batcher(self, arch="qwen2-7b", *, tiny: bool = True, seed: int = 0,
                coded_layers: Optional[str] = None,
                admission: str = "continuous"):
        """The session's :class:`~repro.runtime.serve_loop.ContinuousBatcher`
        for one model and ``coded_layers`` setting (default: the spec's),
        built on first use and cached — compiled step programs,
        pre-encoded serving weights and warm buckets are reused, so a
        second serve with the same shapes retraces NOTHING.  ``arch`` is
        an arch name or a ``ModelConfig``, as in :meth:`serve`."""
        self._check_open()
        from ..runtime.serve_loop import ContinuousBatcher
        model, params, mkey = self._serve_model(arch, tiny, seed)
        serve_spec = self.spec.serve
        if coded_layers is None:
            coded_layers = serve_spec.coded_layers
        bkey = mkey + (coded_layers, admission)
        bat = self._serve_batchers.get(bkey)
        if bat is None:
            bat = ContinuousBatcher(
                self.engine, model, params, coded_layers=coded_layers,
                max_slots=serve_spec.max_slots, eos_id=serve_spec.eos_id,
                backend=self.spec.transport.backend, admission=admission)
            self._serve_batchers[bkey] = bat
        return bat

    def serve(self, arch="qwen2-7b", *, tiny: bool = True,
              batch: Optional[int] = None, prompt_len: int = 16,
              gen: int = 32, seed: int = 0, check_agreement: bool = True,
              requests=None, arrival_rate: float = 0.0,
              ragged: bool = False, admission: str = "continuous",
              record_logits: bool = False) -> ServeReport:
        """Continuous-batching greedy decode with every selected
        projection run as coded rounds (``ServeSpec.coded_layers``).

        ``arch`` is an arch name (reduced to its tiny config when
        ``tiny``) or a ``ModelConfig``, which is served as given — the way
        a configuration cut to one chip's share reaches the normal path.

        Requests are served off an arrival timeline by the scheduler in
        :mod:`repro.runtime.serve_loop`: free slots admit arrivals at
        step boundaries, finished/EOS requests are evicted and their
        slots refilled, and the jitted step only sees pow2 batch buckets
        so slot churn never recompiles.  On the virtual transport the
        WHOLE step — attention q/k/v/o, FFN up/down, unembed, per the
        spec's ``coded_layers`` — is ONE coded round under one straggler
        plan and the spec's wait policy; with
        ``WaitSpec(policy="deadline", t_budget=...)`` every step decodes
        at (or before) the budget from whatever responder prefix arrived.
        Real transports (threads/socket) keep the PR 5 semantics: the
        unembed projection as one real round per step.

        ``requests`` (a list of :class:`~repro.runtime.serve_loop.Request`)
        overrides the synthetic workload; otherwise ``batch`` requests of
        ``prompt_len``/``gen`` arrive Poisson at ``arrival_rate`` req/s
        (0 = all at t=0 — the legacy fixed-batch shape; with a uniform
        workload ``tokens`` is exactly (batch, gen)).
        ``admission="gated"`` reproduces the static-batch baseline.
        ``record_logits`` keeps every step's logits per request
        (``ServedRequest.logits``) for parity checks.
        """
        self._check_open()
        from ..runtime.serve_loop import poisson_workload

        serve_spec = self.spec.serve
        n_req = batch if batch is not None else serve_spec.max_slots
        if requests is None:
            model, _, _ = self._serve_model(arch, tiny, seed)
            requests = poisson_workload(
                n_req, rate_rps=arrival_rate, prompt_len=prompt_len,
                gen=gen, vocab=model.cfg.vocab_size, seed=seed,
                ragged=ragged)

        def run_loop(coded_layers: str, record: bool = False):
            bat = self.batcher(arch, tiny=tiny, seed=seed,
                               coded_layers=coded_layers,
                               admission=admission)
            bat._round = self._round
            res = bat.run(requests, record_logits=record)
            self._round = bat._round
            return res

        res = run_loop(serve_spec.coded_layers, record_logits)
        # token matrix, -1 padded for ragged generation lengths
        max_gen = max((len(r.tokens) for r in res.requests), default=0)
        tokens = np.full((len(res.requests), max_gen), -1, np.int32)
        for i, r in enumerate(res.requests):
            tokens[i, :len(r.tokens)] = r.tokens

        # fidelity diagnostic OUTSIDE the serve accounting: greedy tokens
        # of a request depend only on its own prompt, so the uncoded
        # reference is one plain continuous-batching replay of the same
        # workload.  Production-shaped callers pass check_agreement=False
        # (agreement reports NaN).
        agree = float("nan")
        if check_agreement:
            if res.mode == "plain":
                agree = 1.0
            else:
                ref = run_loop("none")
                match = total = 0
                for a, b_ in zip(res.requests, ref.requests):
                    n = min(len(a.tokens), len(b_.tokens))
                    match += int(np.sum(a.tokens[:n] == b_.tokens[:n]))
                    total += max(len(a.tokens), len(b_.tokens))
                agree = match / max(total, 1)
        self.round_stats.extend(res.step_stats)
        return ServeReport(
            tokens=tokens, step_stats=res.step_stats,
            wall_s=res.busy_wall_s, tok_s=res.tok_s,
            t_budget=self.spec.wait.t_budget, argmax_agreement=agree,
            requests=res.requests, ttft_s=res.ttft_s,
            step_latency_s=res.step_virtual_s, p50_step_s=res.p50_step_s,
            p99_step_s=res.p99_step_s, requests_per_s=res.requests_per_s,
            virtual_s=res.virtual_s, busy_wall_s=res.busy_wall_s,
            coded_fraction=res.coded_fraction, trace_count=res.trace_count,
            mode=res.mode)
