"""The coded-round engine behind every front door.

``RoundEngine`` executes coded A@B rounds for ONE declarative
``repro.api.ClusterSpec``: scheme construction, wait policy, transport
selection, crypto mode, straggler environment and encode pipelining all
come off the spec.  Consumers never construct it with loose knobs:

* ``repro.api.Session`` — the public context-managed surface (owns the
  engine's lifecycle, adds ``train_step`` / ``serve``);
* ``repro.runtime.master_worker.DistributedMatmul`` — the legacy
  constructor, now a thin kwargs→spec shim over this engine (outputs
  bit-identical to the pre-spec implementation, asserted in tests).

Execution paths per round (unchanged semantics from the pre-spec
runtime, plus the encrypted anytime round):

* **fused**: encode → all N worker matmuls → masked decode in ONE jitted
  dispatch, LRU-cached per shape class (virtual clock).
* **fused real** (the default for ``encrypt="real"`` on fused rounds):
  the SAME one dispatch with the MEA-ECC wire fused in — keystream +
  limb mask-add/sub run inside the round program
  (``kernels.encrypted_round``); ``CryptoSpec.fused`` knob.
* **staged real** (``crypto.fused=False`` or loop-path schemes): the
  round split at its wire boundaries so genuine MEA-ECC ciphertexts
  cross between three jitted stages.
* **anytime** (proxy-driven policies): 2 jitted dispatches — stage 1
  worker results, stage 2 every responder prefix decoded + embedded-pair
  error proxies in one batched contraction.
* **anytime real**: stage 1 split at the wire (encrypted shards out,
  encrypted results back per arrival), stage 2 unchanged — ``ErrorTarget``
  over genuine ciphertexts with *measured* ``crypto_s``.
* **loop**: the per-worker oracle path (pair-coded schemes,
  ``fused=False``, and the real-thread transport).
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import itertools
import mmap
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .faults import (DegradedRoundError, FaultInjectingTransport,
                     ResultDropped, WorkerHealth, retry_round_index,
                     _BACKOFF_STREAM)
from .spans import span
from .scheduler import (EncodePipeline, assemble_curve, plan_round,
                        retry_backoff, screen_responders, virtual_events)
from .tasks import (EnvelopeMatmulTask, MatmulTask, PairMatmulTask,
                    SealedMatmulTask)
from .transport import (ThreadTransport, VirtualClockTransport,
                        build_transport)
from .wait_policy import (RoundContext, WaitPolicy, resolve_policy,
                          scheme_min_responders)

__all__ = ["RoundStats", "WorkerPool", "RoundEngine"]


@dataclasses.dataclass
class RoundStats:
    encode_s: float
    compute_wait_s: float
    decode_s: float
    crypto_s: float = 0.0
    n_waited: int = 0
    # modeled MEA-ECC estimate kept as a cross-check when ``crypto_s`` is a
    # real measurement (encrypt="real"); 0 otherwise
    crypto_modeled_s: float = 0.0
    # --- event-driven round timeline (scheduler) -------------------------
    policy: str = "fixed_quantile"   # wait policy that picked the prefix
    arrivals: tuple = ()             # ((virtual_t_s, worker), ...) sorted
    decode_at_s: float = 0.0         # virtual time the decode fired
    pipelined_s: float = 0.0         # encode wall time hidden in the
                                     # previous round's wait window
    # jitted dispatches the master's pipeline issued this round (counted at
    # the call sites, not asserted from structure): 1 for a fused round —
    # plain OR encrypted — 2 for the anytime pipeline, 3 + 2·(N + |resp|)
    # for the staged real round.  0 on the loop path (per-worker oracle
    # calls aren't round dispatches).
    dispatches: int = 0
    # --- fault-tolerant round (runtime.faults; FaultSpec.handle) ---------
    retries: int = 0                 # re-dispatch attempts this round
    excluded: tuple = ()             # workers evicted by residual screening
    quarantined: tuple = ()          # workers quarantined at round start
    degraded: bool = False           # decoded below the policy's target
    achieved_rel_err: Optional[float] = None   # embedded-pair estimate of
                                     # a degraded decode's error (rateless)
    decode_mask: tuple = ()          # (N,) 0/1 — slots that entered decode

    @property
    def total_s(self):
        return (self.encode_s + self.compute_wait_s + self.decode_s +
                self.crypto_s - self.pipelined_s)


class WorkerPool:
    """N simulated workers behind the event-driven round API.

    The pool is a facade over the registered transports (see
    ``runtime.transport``): the analytic virtual clock, the real-thread
    backend with one long-lived executor, and the socket process mesh.
    ``real_threads`` survives as a flippable property consulted per
    round, so callers can still flip a pool between the virtual clock
    and real backends mid-life (the tests validating the clock do).
    """

    def __init__(self, n_workers: int, straggler, real_threads: bool = False,
                 *, backend: Optional[str] = None, transport_options=None):
        self.n = n_workers
        self.straggler = straggler
        self._backend = backend if backend is not None else \
            ("threads" if real_threads else "virtual")
        self._options = dict(transport_options or {})
        self._virtual = VirtualClockTransport(straggler)
        self._threads = ThreadTransport(n_workers, straggler)
        self._socket = None     # the process mesh is built (and its
                                # workers spawned) only when first used

    @property
    def backend(self) -> str:
        return self._backend

    @property
    def real_threads(self) -> bool:
        """True when rounds run on a real (non-virtual) backend."""
        return self._backend != "virtual"

    @real_threads.setter
    def real_threads(self, value) -> None:
        # legacy flip: True selects threads (never silently the mesh),
        # False returns to the virtual clock
        if bool(value):
            if self._backend == "virtual":
                self._backend = "threads"
        else:
            self._backend = "virtual"

    @property
    def transport(self):
        """The backend the next round runs on."""
        if self._backend == "socket":
            if self._socket is None:
                self._socket = build_transport("socket", self.n,
                                               self.straggler,
                                               **self._options)
            return self._socket
        return self._threads if self._backend == "threads" else self._virtual

    @property
    def _executor(self):
        # surfaced for lifecycle tests: the thread transport's executor,
        # None when closed / never used
        return self._threads._executor

    def close(self):
        """Shut the real transports down (stragglers of the last round
        included, worker processes terminated within their bounded
        deadline); surfaces any failure an unconsumed straggler hit after
        its round.  Idempotent."""
        self._threads.close()
        if self._socket is not None:
            self._socket.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def run_round(self, shards, f, round_idx: int, wait_for: int,
                  t_compute: Optional[float] = None):
        """shards: list of per-worker inputs (or (a,b) tuples).  Returns
        (responder_indices, results_in_responder_order, wait_seconds).

        ``t_compute`` is the virtual-clock per-task compute time; the
        caller owns the latency model (``RoundEngine`` passes the same
        once-per-shape timed batched call for fused and loop rounds, so
        cross-scheme comparisons price workers identically).  Ignored in
        real-thread mode, required otherwise.
        """
        if self.real_threads:
            events, done, elapsed = self.run_round_real(
                shards, f, round_idx, stop_after=wait_for)
            resp = np.sort(np.asarray([e.worker for e in events[:wait_for]],
                                      dtype=np.int64))
            return resp, [done[i] for i in resp], elapsed

        # virtual clock: only the selected responders' work actually runs
        # (stragglers the policy never picks cost nothing)
        if t_compute is None:
            raise ValueError("virtual-clock run_round needs t_compute "
                             "(see RoundEngine._worker_compute_time)")
        handle = self._virtual.submit_round(shards, f, round_idx,
                                            t_compute=t_compute)
        events = list(itertools.islice(handle.events(), int(wait_for)))
        resp = np.sort(np.asarray([e.worker for e in events],
                                  dtype=np.int64))
        return resp, [handle.result(i) for i in resp], float(events[-1].t)

    def run_round_real(self, shards, f, round_idx: int,
                       policy: Optional[WaitPolicy] = None, scheme=None,
                       n_stragglers: int = 0,
                       stop_after: Optional[int] = None):
        """Event-driven real-thread round.

        Drains the thread transport's completion stream until
        ``policy.satisfied`` — or after ``stop_after`` arrivals when
        given.  Returns (events_consumed, {worker: result}, elapsed_s);
        stragglers the policy never waited for keep running and are
        discarded.  Policies that need per-prefix error proxies
        (ErrorTarget) are a virtual-clock feature — real mode exists to
        validate the clock.
        """
        if policy is not None and policy.needs_proxy:
            raise NotImplementedError(
                f"{policy.name}: proxy-driven policies run on the virtual "
                "clock (real-thread mode validates the clock)")
        budget = getattr(policy, "t_budget", None)
        min_ready = scheme_min_responders(scheme) if scheme is not None else 1
        # the pool's selected real backend (threads or the socket mesh);
        # direct callers on a virtual pool get the thread transport, the
        # pre-mesh behaviour
        transport = self.transport if self.real_threads else self._threads
        handle = transport.submit_round(shards, f, round_idx,
                                        budget=budget,
                                        min_ready=min_ready)
        events = []
        try:
            for ev in handle.events():
                events.append(ev)
                if stop_after is not None:
                    if len(events) >= max(int(stop_after), 1):
                        break
                    continue
                if policy is not None and len(events) >= min_ready:
                    ctx = RoundContext(scheme=scheme,
                                       n_stragglers=n_stragglers,
                                       events=events, min_ready=min_ready)
                    if policy.satisfied(ctx):
                        break
        finally:
            elapsed = handle.finish()
        done = {e.worker: handle.result(e.worker) for e in events}
        return events, done, elapsed


# On a TPU host ``np.asarray`` of a product over 32 MB writes it into freshly
# mapped pages (glibc maps every block over 32 MB anew), and one thread
# faulting those pages in costs more than the copy: on a v5e host, 15.3 ms
# for 36 MB against 2.96 ms for 24 MB.  So a fused round's product of at least
# ``_CHUNK_MIN_BYTES`` leaves the round program on a TPU as up to
# ``_COPY_THREADS`` row chunks, and as many host threads map the pages of
# one fresh array while the chip computes, then copy the chunks into it.
# Smaller products copy faster whole, and on the CPU ``np.asarray`` of the
# whole product is close to free.
_CHUNK_PLATFORMS = ("tpu",)
_CHUNK_MIN_BYTES = 32 << 20
_COPY_THREADS = 8


def _row_cuts(m: int, n: int, itemsize: int, platform: str) -> tuple:
    """Row indices at which the round program splits its ``(m, n)``
    product: multiples of 8 rows (the TPU tile's), empty for a product
    that leaves whole."""
    k = min(_COPY_THREADS, m // 8)
    if (platform not in _CHUNK_PLATFORMS or k < 2
            or m * n * itemsize < _CHUNK_MIN_BYTES):
        return ()
    step = -(-m // k // 8) * 8
    return tuple(range(step, m, step))


def _split_rows(prod, cuts: tuple):
    """The round program's output: the product, or its row chunks."""
    return tuple(jnp.split(prod, cuts)) if cuts else prod


class RoundEngine:
    """Coded A@B rounds for one ``ClusterSpec`` (see module docstring).

    ``straggler`` / ``policy`` accept pre-built instances for callers
    holding objects the spec can't express (a hand-built
    ``StragglerModel``, a custom ``WaitPolicy`` subclass) — the legacy
    shim passes its instances straight through so outputs stay
    bit-identical to the pre-spec runtime.
    """

    def __init__(self, spec, *, straggler=None, policy=None):
        self.spec = spec
        self.name = spec.code.scheme
        self.n = spec.code.n_workers
        self.k = spec.code.k_blocks
        self.t = spec.privacy.t_colluding
        mode = spec.crypto.encrypt
        self.encrypt = mode
        self.straggler = straggler if straggler is not None else \
            spec.straggler.build(self.n, spec.seed)
        self.pool = WorkerPool(
            self.n, self.straggler,
            backend=spec.transport.backend,
            transport_options=spec.transport.backend_options())
        self.scheme = spec.build_scheme()
        spec.validate(scheme=self.scheme)
        # the decode point is a pluggable WaitPolicy; the default
        # FixedQuantile reproduces the seed's fixed-count wait (and its
        # responder selection) bit-identically through the event scheduler
        self.policy = resolve_policy(policy if policy is not None
                                     else spec.wait.build())
        # the embedded-pair proxy decoder's Floater–Hormann degree — a
        # first-class decode config (WaitSpec.fh_degree, default 2 from the
        # BENCH_anytime parity-oscillation notes)
        self.fh_degree = spec.wait.fh_degree
        self.wait_for = self.scheme.wait_policy(self.straggler.n_stragglers)
        # encode-of-next-round pipelining: the master hides encode wall
        # time inside the previous round's wait window (virtual-clock
        # accounting via RoundStats.pipelined_s); opt-in so the seed's
        # per-round accounting stays unchanged by default
        self._pipeline = EncodePipeline() if spec.pipeline_encode else None
        supports = bool(getattr(self.scheme, "supports_fused", False))
        fused = spec.code.fused
        # default to fused only when the masked decode is also numerically
        # sound in f32 — the pinv of an ill-conditioned (large-K Vandermonde
        # / Lagrange) encoder silently destroys the result, so those
        # schemes keep the exact f64 loop decode unless forced.  The
        # real-thread transport always runs the event-driven loop round.
        stable = bool(getattr(self.scheme, "fused_decode_stable", False))
        self.use_fused = (supports and stable) if fused is None else bool(fused)
        if spec.transport.backend != "virtual":
            # every real backend (threads, socket mesh) runs the
            # event-driven loop round
            self.use_fused = False
        # fault injection / handling (runtime.faults): the injecting
        # transport wraps whichever backend the pool selected — protocol
        # unchanged — and the defended round runs the slot-envelope path
        # (per-worker results are what screening and re-dispatch operate
        # on, so the one-dispatch fused round cannot carry it)
        self.fault = spec.fault
        self.health: Optional[WorkerHealth] = None
        self._fault_transport = None
        if self.fault.active:
            fseed = (self.fault.seed if self.fault.seed is not None
                     else spec.seed)
            self._fault_seed = fseed        # jittered-backoff rng root
            self._fault_transport = FaultInjectingTransport(
                self.pool.transport, self.fault, fseed)
            self.health = WorkerHealth(
                self.n, quarantine_after=self.fault.quarantine_after,
                quarantine_rounds=self.fault.quarantine_rounds)
            self.use_fused = False
        self.trace_count = 0                # jit traces of the fused round
        self._fused_cache = collections.OrderedDict()   # shapes -> jitted fn
        self._fused_cache_max = 8
        self._worker_t = {}                 # shapes -> per-worker seconds
        self._encode_t = {}                 # shapes -> encode-only seconds
        # adaptive redundancy (runtime.adaptive): every jit cache key
        # carries the active scheme's identity token, so a retuned scheme
        # reuses ITS compiled functions instead of tracing fresh ones —
        # retuning cycles the LRU, it never recompiles per round
        self._scheme_token = ("base",)
        self._timed_rounds: set = set()     # (token, shapes) already run
        self.adaptive = None
        ad = getattr(spec, "adaptive", None)
        if ad is not None and ad.enabled:
            from .adaptive import AdaptiveController
            self.adaptive = AdaptiveController(
                ad, self.n, self.scheme, self._build_candidate_scheme,
                seed=spec.seed)
            if self.health is None:
                # the controller blends per-worker EWMA latency into its
                # fits; outside fault mode nothing else creates the tracker
                self.health = WorkerHealth(self.n)
            # every candidate may hold compiled fns for a few shape
            # classes concurrently — size the LRU so retuning cycles
            # between candidates without evicting live entries
            self._fused_cache_max = max(
                8, 4 * (len(self.adaptive.candidates) + 1))
        self._crypto = None
        self._crypto_per_elem = {}          # (dtype, mode) -> seconds/element
        if mode is not None:
            from ..crypto import MEAECC, generate_keypair
            # per-element rate sample for the modeled estimate (the seed
            # behaviour; in "real" mode it survives as a cross-check)
            self._crypto = (MEAECC(mode=spec.crypto.cipher_mode),
                            generate_keypair())
        if mode == "real":
            from ..crypto import MEAECC, generate_keypair
            # the transport cipher: lossless bits codec + static session
            # keys, so decrypt(encrypt(x)) is bit-identical to x and the
            # per-message EC cost is one cached shared-point lookup.
            # cipher_mode defaults to "stream" — on a static channel the
            # paper's single-mask mode would reuse one mask for every
            # message; cipher_mode="paper" stays available for studying
            # the paper-faithful construction (see README "Security")
            self._mea = MEAECC(mode=spec.crypto.cipher_mode, codec="bits")
            self._master_kp = generate_keypair()
            self._worker_kps = [generate_keypair() for _ in range(self.n)]
            self._nonce = itertools.count(1)
            # one-dispatch encrypted rounds: the wire runs INSIDE the fused
            # round program (kernels.encrypted_round).  ECDH is symmetric,
            # so one cached shared point per worker covers both directions.
            from ..crypto.ecc import shared_secret
            self._shared_pts = [shared_secret(self._mea.curve,
                                              self._master_kp, kp.pk)
                                for kp in self._worker_kps]
            cf = spec.crypto.fused
            self._crypto_fused = self.use_fused if cf is None else bool(cf)
            if spec.crypto.cipher_mode == "paper":
                # paper mode: one static Ψ per channel (the mask the staged
                # path derives), reused every round — precompute the stack
                self._psi_limbs = np.stack(
                    [self._mea._mask_material(pt, None, "paper")
                     for pt in self._shared_pts])
            self._fused_crypto_t = {}       # shapes -> measured wire seconds
        self.dispatch_count = 0             # jitted dispatches, all rounds
        self._copy_pool = None              # threads of _start_copy

    def close(self):
        """Release the pool's long-lived executor.  Idempotent — the
        Session context manager calls this exactly once on exit, but a
        second call is safe."""
        self.pool.close()
        if self._copy_pool is not None:
            self._copy_pool.shutdown()
            self._copy_pool = None

    def _start_copy(self, out):
        """Start the copy of a fused round's product to the host, right
        after its dispatch.  The function returned, called once the product
        is ready, ends the copy and gives one read-only ``(m, n)`` array,
        as ``np.asarray`` of a device array is.  A product that left the
        program in row chunks (:func:`_split_rows`) is copied by one thread
        per chunk into its rows of one fresh array; each thread first maps
        its rows' pages, while the chip still computes the product."""
        chunks = out if isinstance(out, tuple) else (out,)
        if len(chunks) > 1:
            rows = [c.shape[0] for c in chunks]
            host = np.empty((sum(rows),) + chunks[0].shape[1:],
                            chunks[0].dtype)
            page = max(1, mmap.PAGESIZE // host.itemsize)

            def copy(start, chunk):
                dst = host[start:start + chunk.shape[0]]
                dst.reshape(-1)[::page] = 0
                np.copyto(dst, np.asarray(chunk))

            if self._copy_pool is None:
                self._copy_pool = concurrent.futures.ThreadPoolExecutor(
                    _COPY_THREADS, thread_name_prefix="round-copy")
            copies = [self._copy_pool.submit(copy, start, chunk) for
                      start, chunk in zip(np.cumsum([0] + rows[:-1]), chunks)]

        def finish() -> np.ndarray:
            with span("round.to_host", chunked=int(len(chunks) > 1),
                      bytes=sum(c.nbytes for c in chunks)):
                if len(chunks) == 1:
                    return np.asarray(out)
                for c in copies:
                    c.result()
                host.flags.writeable = False
                return host
        return finish

    # ------------------------------------------------------------- crypto
    def _crypto_cost_per_elem(self, dtype) -> float:
        """MEA-ECC seconds per matrix element, measured once per (dtype,
        mode) on a 64×64 sample and cached — the cost is per-element linear.
        A warm-up round trip runs first so jit compilation and the one-time
        EC table builds never leak into the extrapolated rate."""
        mea, kp = self._crypto
        key = (str(dtype), mea.mode)
        if key not in self._crypto_per_elem:
            m = np.zeros((64, 64), dtype)
            ct = mea.encrypt(m, kp.pk)          # warm: compile + tables
            mea.decrypt(ct, kp)
            t0 = time.perf_counter()
            ct = mea.encrypt(m, kp.pk)
            mea.decrypt(ct, kp)
            self._crypto_per_elem[key] = (time.perf_counter() - t0) / m.size
        return self._crypto_per_elem[key]

    def _crypto_overhead_elems(self, total_elems: int, dtype) -> float:
        """Modeled MEA-ECC cost: master encrypt + worker decrypt + result
        encrypt (3 passes) over ``total_elems`` shard elements."""
        if not self._crypto:
            return 0.0
        return self._crypto_cost_per_elem(dtype) * total_elems * 3

    def _crypto_overhead(self, shards) -> float:
        if not self._crypto:
            return 0.0
        a = shards[0][0] if isinstance(shards[0], tuple) else shards[0]
        total_elems = sum(int(np.prod(np.shape(s[0] if isinstance(s, tuple) else s)))
                          for s in shards)
        # dtype off the attribute — np.asarray would round-trip the whole
        # device array to host just to read it
        return self._crypto_overhead_elems(total_elems,
                                           getattr(a, "dtype", np.float32))

    def _wire(self, arr: np.ndarray, sender_kp, recipient_kp) -> np.ndarray:
        """One real master↔worker transfer: MEA-ECC encrypt to the
        recipient's public key, decrypt with its private key at the other
        end.  The bits codec makes the round trip bit-identical; the static
        session keys make the per-message EC cost a cache lookup."""
        self.dispatch_count += 2            # encrypt core + decrypt core
        ct = self._mea.encrypt(np.asarray(arr), recipient_kp.pk,
                               sender=sender_kp, nonce=next(self._nonce))
        return self._mea.decrypt(ct, recipient_kp)

    def _fused_mask_material(self):
        """Per-round mask material stacks for the one-dispatch encrypted
        round: (material_out, material_back), each (N, 8) PRF seed words
        (stream — fresh nonce per channel per direction, same nonce stream
        the staged ``_wire`` draws from) or the static (N, L) Ψ limb stack
        (paper).  Host-side numpy; everything downstream is traced."""
        if self._mea.mode == "paper":
            return self._psi_limbs, self._psi_limbs
        from ..crypto.field import seed_words
        out = np.stack([seed_words(pt.x, pt.y, next(self._nonce))
                        for pt in self._shared_pts])
        back = np.stack([seed_words(pt.x, pt.y, next(self._nonce))
                         for pt in self._shared_pts])
        return out, back

    def _fused_crypto_time(self, blk: int, d: int, n_out: int) -> float:
        """Measured wall seconds of the round's wire work alone — the two
        in-trace cipher applications (shards out, results back) at this
        round's payload shapes, timed once per shape class on a jitted
        wire-only program and cached.  ``RoundStats.crypto_s`` attribution
        for the fused timeline: the fused round has no wire boundary to
        put a timer on, so the cost is measured where it can be isolated
        and subtracted from the master's single-dispatch wall time."""
        key = (blk, d, n_out)
        if key not in self._fused_crypto_t:
            from ..kernels.encrypted_round import wire_roundtrip
            mode = self._mea.mode
            q = self._mea.curve.q
            mat_out, mat_back = self._fused_mask_material()

            def _wires(x_out, x_back, mo, mb):
                return (wire_roundtrip(x_out, mo, q=q, mode=mode),
                        wire_roundtrip(x_back, mb, q=q, mode=mode))

            fn = jax.jit(_wires)
            args = (jnp.zeros((self.n, blk, d), jnp.float32),
                    jnp.zeros((self.n, blk, n_out), jnp.float32),
                    jnp.asarray(mat_out), jnp.asarray(mat_back))
            jax.block_until_ready(fn(*args))           # compile
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            self._fused_crypto_t[key] = time.perf_counter() - t0
        return self._fused_crypto_t[key]

    # ------------------------------------------------------- fused pipeline
    def _jit(self, fn, name: str):
        """``jax.jit`` of one round program: each trace bumps
        ``trace_count`` and is marked by a ``spacdc.trace`` span, so a
        dispatch that recompiled shows in the profiler's trace.  The
        program keeps ``fn``'s name (``jit_<fn.__name__>``)."""
        @functools.wraps(fn)
        def traced(*args):
            self.trace_count += 1          # runs at trace time only
            with span("trace", fn=name):
                return fn(*args)
        return jax.jit(traced)

    def _fused_fn(self, a_shape, b_shape, dtype):
        """The jitted round for one shape class, LRU-cached.  The straggler
        mask is a traced argument, so responder churn never recompiles."""
        key = (self._scheme_token, a_shape, b_shape, dtype)
        fn = self._fused_cache.get(key)
        if fn is None:
            scheme = self.scheme
            m, n_out = a_shape[0], b_shape[-1]
            cuts = _row_cuts(m, n_out, jnp.dtype(dtype).itemsize,
                             jax.default_backend())

            def _round(a, b, mask):
                decoded = scheme.fused_round(a, b, mask)
                return _split_rows(
                    scheme.reconstruct_matmul(decoded, m, n_out), cuts)

            fn = self._jit(_round, "fused_round")
            self._fused_cache[key] = fn
            if len(self._fused_cache) > self._fused_cache_max:
                self._fused_cache.popitem(last=False)
        else:
            self._fused_cache.move_to_end(key)
        return fn

    def _staged_fns(self, a_shape, b_shape, dtype):
        """The real-encryption round, split at the wire boundaries into
        three jitted stages (encode / batched worker matmul / masked decode)
        — each LRU-cached per shape class, so the fused path still compiles
        once per shape class while genuine ciphertexts cross between the
        stages.  The stages mirror ``kernels.ref.coded_matmul`` op-for-op,
        so a real round is bit-identical to the single-dispatch round."""
        key = ("real", self._scheme_token, a_shape, b_shape, dtype)
        fns = self._fused_cache.get(key)
        if fns is None:
            scheme = self.scheme
            m, n_out = a_shape[0], b_shape[-1]

            def _encode(a):
                return scheme.encode(a)

            def _workers(blocks, b):
                return jnp.einsum(
                    "nij,jk->nik", blocks.astype(jnp.float32),
                    b.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST).astype(jnp.float32)

            def _decode(results, mask):
                dec = scheme._combine(scheme.decode_matrix_masked(mask),
                                      results)
                return scheme.reconstruct_matmul(dec, m, n_out)

            fns = (self._jit(_encode, "staged_encode"),
                   self._jit(_workers, "staged_workers"),
                   self._jit(_decode, "staged_decode"))
            self._fused_cache[key] = fns
            if len(self._fused_cache) > self._fused_cache_max:
                self._fused_cache.popitem(last=False)
        else:
            self._fused_cache.move_to_end(key)
        return fns

    def _fused_real_fn(self, a_shape, b_shape, dtype):
        """The ONE-dispatch encrypted round for one shape class, LRU-cached:
        encode → MEA-ECC wire-out → batched worker matmul → wire-back →
        masked decode, a single jitted program (``kernels.ops.
        encrypted_coded_matmul`` + the scheme's masked decode).  The
        straggler mask and the per-round mask material (stream nonces) are
        runtime arguments, so responder churn and fresh nonces never
        recompile.  The wire is the lossless bits codec, so the output is
        bit-identical to both the plain fused round and the staged real
        round (same contractions, same precision) — asserted in tests."""
        key = ("real_fused", self._scheme_token, a_shape, b_shape, dtype)
        fn = self._fused_cache.get(key)
        if fn is None:
            scheme = self.scheme
            m, n_out = a_shape[0], b_shape[-1]
            from ..kernels.ops import encrypted_coded_matmul
            enc = jnp.asarray(scheme.fused_encoder_matrix(), jnp.float32)
            q, mode = self._mea.curve.q, self._mea.mode
            cuts = _row_cuts(m, n_out, jnp.dtype(dtype).itemsize,
                             jax.default_backend())

            def _round(a, b, mask, mat_out, mat_back):
                results = encrypted_coded_matmul(
                    enc, scheme.fused_blocks(a), b, mat_out, mat_back,
                    q=q, mode=mode, force_kernel=scheme.use_kernel)
                dec = scheme._combine(scheme.decode_matrix_masked(mask),
                                      results)
                return _split_rows(scheme.reconstruct_matmul(dec, m, n_out),
                                   cuts)

            fn = self._jit(_round, "real_fused_round")
            self._fused_cache[key] = fn
            if len(self._fused_cache) > self._fused_cache_max:
                self._fused_cache.popitem(last=False)
        else:
            self._fused_cache.move_to_end(key)
        return fn

    def _worker_compute_time(self, lhs_shape, rhs_shape) -> float:
        """Virtual-clock per-worker latency: time ONE jitted batched matmul
        of the per-worker operand shapes (once per shape, cached) and
        divide by N — the N workers of the real system run concurrently.
        Both the fused and loop paths price workers through this same
        model, so cross-scheme comparisons measure the codes, not
        host-dispatch noise."""
        key = (tuple(lhs_shape), tuple(rhs_shape))
        if key not in self._worker_t:
            lhs = jnp.zeros((self.n,) + tuple(lhs_shape), jnp.float32)
            rhs = jnp.zeros((self.n,) + tuple(rhs_shape), jnp.float32)
            batched = jax.jit(lambda l, r: jnp.einsum("nij,njk->nik", l, r))
            jax.block_until_ready(batched(lhs, rhs))         # compile
            t0 = time.perf_counter()
            jax.block_until_ready(batched(lhs, rhs))
            self._worker_t[key] = (time.perf_counter() - t0) / self.n
        return self._worker_t[key]

    def _round_compute_time(self, a_shape, b_shape):
        """(block rows, per-worker virtual compute seconds) for this job."""
        split = getattr(self.scheme, "k_blocks", self.n)
        blk = -(-a_shape[0] // split)
        return blk, self._worker_compute_time((blk, a_shape[1]),
                                              (a_shape[1], b_shape[-1]))

    def _virtual_round_plan(self, a_shape, b_shape, round_idx: int,
                            proxy_fn=None):
        """Virtual clock: the round's arrival timeline and the prefix the
        wait policy consumes.  Shared by the fused and real-encryption
        paths so their responder selection can never desynchronize (the
        real round is asserted bit-identical to the unencrypted one)."""
        with span("round.plan"):
            blk, t_comp = self._round_compute_time(a_shape, b_shape)
            plan = plan_round(self.scheme, self.policy,
                              self.straggler.delays(round_idx), t_comp,
                              self.straggler.n_stragglers, proxy_fn=proxy_fn)
        return blk, plan

    # ------------------------------------------------------------- serving
    # Minimal public hooks the continuous-batching serve loop
    # (``runtime.serve_loop``) builds on.  The loop owns its own step
    # programs (a whole decode step — every coded site — is ONE jitted
    # dispatch), but prices workers, plans rounds, draws wire material and
    # attributes crypto time through the same machinery as every other
    # round, so serve RoundStats stay comparable with matmul rounds.

    def worker_time(self, lhs_shape, rhs_shape) -> float:
        """Per-worker virtual seconds for one coded site's matmul."""
        return self._worker_compute_time(lhs_shape, rhs_shape)

    def serve_round_plan(self, round_idx: int, t_comp: float):
        """Straggler plan for one serve step treated as ONE coded round.
        ``t_comp`` is the per-worker compute of every coded site in the
        step, summed — each worker runs all of its site shards
        back-to-back before replying."""
        return plan_round(self.scheme, self.policy,
                          self.straggler.delays(round_idx), t_comp,
                          self.straggler.n_stragglers)

    def serve_wire_params(self):
        """(q, cipher_mode) for in-step ``wire_roundtrip`` calls, or None
        when this spec doesn't run real encryption."""
        if getattr(self, "_mea", None) is None:
            return None
        return self._mea.curve.q, self._mea.mode

    def serve_wire_material(self, count: int):
        """``count`` fresh (out, back) wire-material pairs — one pair per
        coded site instance in a serve step (stream mode draws fresh
        nonces per site per step from the same nonce stream as the staged
        wire; paper mode returns the static Ψ stack).  Each side is
        (count, N, W) numpy."""
        outs, backs = zip(*(self._fused_mask_material()
                            for _ in range(count)))
        return np.stack(outs), np.stack(backs)

    def serve_crypto_time(self, elems_out: int, elems_back: int) -> float:
        """Measured wall seconds of ONE serve step's wire work alone: the
        per-channel payloads of every coded site, flattened to (N, elems)
        and timed on a jitted wire-only program once per element-count
        class (the serve analogue of :meth:`_fused_crypto_time` — the
        in-step wire has no boundary to put a timer on)."""
        key = ("serve", elems_out, elems_back)
        if key not in self._fused_crypto_t:
            from ..kernels.encrypted_round import wire_roundtrip
            mode = self._mea.mode
            q = self._mea.curve.q
            mat_out, mat_back = self._fused_mask_material()

            def _wires(x_out, x_back, mo, mb):
                return (wire_roundtrip(x_out, mo, q=q, mode=mode),
                        wire_roundtrip(x_back, mb, q=q, mode=mode))

            fn = jax.jit(_wires)
            args = (jnp.zeros((self.n, max(elems_out, 1)), jnp.float32),
                    jnp.zeros((self.n, max(elems_back, 1)), jnp.float32),
                    jnp.asarray(mat_out), jnp.asarray(mat_back))
            jax.block_until_ready(fn(*args))           # compile
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            self._fused_crypto_t[key] = time.perf_counter() - t0
        return self._fused_crypto_t[key]

    def _encode_only_time(self, a_shape) -> float:
        """Measured wall seconds of ONE jitted encode at this shape
        (cached).  Caps the pipelining credit on paths whose master timer
        lumps encode with decode/reassembly: only the encode can genuinely
        overlap the previous round's wait window — this round's decode
        needs this round's results."""
        key = (self._scheme_token, tuple(a_shape))
        if key not in self._encode_t:
            fn = jax.jit(self.scheme.encode)
            z = jnp.zeros(a_shape, jnp.float32)
            jax.block_until_ready(fn(z))               # compile
            t0 = time.perf_counter()
            jax.block_until_ready(fn(z))
            self._encode_t[key] = time.perf_counter() - t0
        return self._encode_t[key]

    def _account_encode(self, encode_s: float, wait_s: float) -> float:
        """Encode-pipelining credit: how much of this round's encode hid
        in the previous round's wait window (and bank this round's)."""
        if self._pipeline is None:
            return 0.0
        _, hidden = self._pipeline.charge(encode_s)
        self._pipeline.credit(wait_s)
        return hidden

    def _stats(self, events, decode_at_s: float, **kw) -> RoundStats:
        kw.setdefault("policy", self.policy.name)
        kw.setdefault("arrivals", tuple((e.t, e.worker) for e in events))
        kw.setdefault("decode_at_s", decode_at_s)
        return RoundStats(**kw)

    def _matmul_fused(self, a: jnp.ndarray, b: jnp.ndarray, round_idx: int):
        fn = self._fused_fn(a.shape, b.shape, str(a.dtype))
        blk, plan = self._virtual_round_plan(a.shape, b.shape, round_idx)
        # master math (encode + decode + reassembly): one dispatch
        t0 = time.perf_counter()
        with span("round.dispatch"):
            out = fn(a, b, jnp.asarray(plan.mask))
        self.dispatch_count += 1
        copy = self._start_copy(out)
        with span("round.wait"):
            jax.block_until_ready(out)
        t_master = time.perf_counter() - t0
        crypto_s = self._crypto_overhead_elems(self.n * blk * a.shape[1],
                                               np.float32)
        hideable = (0.0 if self._pipeline is None else
                    min(t_master, self._encode_only_time(a.shape)))
        stats = self._stats(plan.events, plan.wait_s, encode_s=t_master,
                            compute_wait_s=plan.wait_s, decode_s=0.0,
                            crypto_s=crypto_s, n_waited=len(plan.responders),
                            dispatches=1,
                            pipelined_s=self._account_encode(hideable,
                                                             plan.wait_s))
        return copy(), stats

    def _matmul_real_fused(self, a: jnp.ndarray, b: jnp.ndarray,
                           round_idx: int):
        """The encrypted round as ONE dispatch: the wire runs inside the
        fused round program (see :meth:`_fused_real_fn`), so an encrypted
        round costs one jitted dispatch exactly like a plain round —
        versus the staged path's three stages plus two cipher-core
        dispatches per transfer.  ``crypto_s`` is attributed from the
        fused timeline: the wire work is timed in isolation once per shape
        class (:meth:`_fused_crypto_time`) and subtracted from the
        master's single-dispatch wall time; the modeled estimate rides
        along in ``crypto_modeled_s`` as a cross-check."""
        fn = self._fused_real_fn(a.shape, b.shape, str(a.dtype))
        blk, plan = self._virtual_round_plan(a.shape, b.shape, round_idx)
        mat_out, mat_back = self._fused_mask_material()
        t0 = time.perf_counter()
        with span("round.dispatch"):
            out = fn(a, b, jnp.asarray(plan.mask), jnp.asarray(mat_out),
                     jnp.asarray(mat_back))
        self.dispatch_count += 1
        copy = self._start_copy(out)
        with span("round.wait"):
            jax.block_until_ready(out)
        t_master = time.perf_counter() - t0
        crypto_s = min(self._fused_crypto_time(blk, a.shape[1], b.shape[-1]),
                       t_master)
        modeled = self._crypto_overhead_elems(self.n * blk * a.shape[1],
                                              np.float32)
        encode_s = t_master - crypto_s
        hideable = (0.0 if self._pipeline is None else
                    min(encode_s, self._encode_only_time(a.shape)))
        stats = self._stats(plan.events, plan.wait_s, encode_s=encode_s,
                            compute_wait_s=plan.wait_s, decode_s=0.0,
                            crypto_s=crypto_s, n_waited=len(plan.responders),
                            crypto_modeled_s=modeled, dispatches=1,
                            pipelined_s=self._account_encode(hideable,
                                                             plan.wait_s))
        return copy(), stats

    def _staged_stage1(self, a, b, enc_fn, worker_fn):
        """Encode, wire every coded shard to its worker (MEA-ECC), run the
        batched worker matmul on the decrypted — bit-identical — shards.
        The shared first half of every real-encryption round.  Returns
        (results, master_compute_s, crypto_out_s); ``results`` is a
        writable numpy copy so responder slots can be overwritten with
        their decrypted wire payloads."""
        t0 = time.perf_counter()
        self.dispatch_count += 1
        enc = np.asarray(enc_fn(a))                      # (N, blk, d)
        t_enc = time.perf_counter() - t0
        # wire out: each worker receives (and decrypts) its coded shard
        t0 = time.perf_counter()
        shards = np.stack([self._wire(enc[i], self._master_kp,
                                      self._worker_kps[i])
                           for i in range(self.n)])
        crypto_out = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.dispatch_count += 1
        results = np.array(worker_fn(jnp.asarray(shards), b))
        t_enc += time.perf_counter() - t0
        return results, t_enc, crypto_out

    def _proxy_stop(self, events, prox) -> int:
        """The proxy-driven policy's stop prefix for one round timeline."""
        ctx = RoundContext(scheme=self.scheme,
                           n_stragglers=self.straggler.n_stragglers,
                           events=events,
                           min_ready=scheme_min_responders(self.scheme),
                           proxies=prox)
        return int(self.policy.stop_index(ctx))

    def _matmul_real(self, a: jnp.ndarray, b: jnp.ndarray, round_idx: int):
        """The fused round with genuine transmission security: every shard
        is MEA-ECC-encrypted to its worker and decrypted there, every
        responder's product is encrypted back to the master — ``crypto_s``
        is the *measured* wall time of those transfers (the modeled
        estimate rides along in ``crypto_modeled_s`` as a cross-check).
        The bits-codec transport is lossless, so the round output is
        bit-identical to the unencrypted round."""
        enc_fn, worker_fn, decode_fn = self._staged_fns(a.shape, b.shape,
                                                        str(a.dtype))
        blk, plan = self._virtual_round_plan(a.shape, b.shape, round_idx)
        resp, wait_s, mask = plan.responders, plan.wait_s, plan.mask
        d0 = self.dispatch_count
        results, t_enc, crypto_s = self._staged_stage1(a, b, enc_fn,
                                                       worker_fn)
        # wire back: the responders' products return encrypted (stragglers
        # never answer; their slots carry weight 0 in the masked decode)
        t0 = time.perf_counter()
        for i in resp:
            results[i] = self._wire(results[i], self._worker_kps[i],
                                    self._master_kp)
        crypto_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        self.dispatch_count += 1
        out = decode_fn(jnp.asarray(results), jnp.asarray(mask))
        jax.block_until_ready(out)
        t_dec = time.perf_counter() - t0
        modeled = self._crypto_overhead_elems(self.n * blk * a.shape[1],
                                              np.float32)
        hideable = (0.0 if self._pipeline is None else
                    min(t_enc, self._encode_only_time(a.shape)))
        stats = self._stats(plan.events, wait_s, encode_s=t_enc,
                            compute_wait_s=wait_s, decode_s=t_dec,
                            crypto_s=crypto_s, n_waited=len(resp),
                            crypto_modeled_s=modeled,
                            dispatches=self.dispatch_count - d0,
                            pipelined_s=self._account_encode(hideable,
                                                             wait_s))
        return np.asarray(out), stats

    # ---------------------------------------------------- anytime pipeline
    def _anytime_results_fn(self, a_shape, b_shape, dtype):
        """Jitted stage 1 of the anytime round: encode + ALL N worker
        matmuls in one ``kernels.ops.coded_matmul`` dispatch (no decode —
        the decode point isn't known yet)."""
        key = ("any_results", self._scheme_token, a_shape, b_shape, dtype)
        fn = self._fused_cache.get(key)
        if fn is None:
            scheme = self.scheme
            from ..kernels.ops import coded_matmul
            enc = jnp.asarray(scheme.fused_encoder_matrix(), jnp.float32)

            def _results(a, b):
                return coded_matmul(enc, scheme.fused_blocks(a), b,
                                    force_kernel=scheme.use_kernel)

            fn = self._jit(_results, "anytime_results")
            self._fused_cache[key] = fn
            if len(self._fused_cache) > self._fused_cache_max:
                self._fused_cache.popitem(last=False)
        else:
            self._fused_cache.move_to_end(key)
        return fn

    def _anytime_results_real_fn(self, a_shape, b_shape, dtype):
        """Jitted stage 1 of the ENCRYPTED anytime round: encode + wire-out
        + all N worker matmuls + wire-back, one dispatch (the encrypted
        twin of :meth:`_anytime_results_fn`).  Every worker's product
        crosses the wire in-dispatch — the one-dispatch tradeoff: the
        arrivals past the stop prefix transmit too, where the staged path
        wires back only what the policy consumed."""
        key = ("any_results_real", self._scheme_token, a_shape, b_shape,
               dtype)
        fn = self._fused_cache.get(key)
        if fn is None:
            scheme = self.scheme
            from ..kernels.ops import encrypted_coded_matmul
            enc = jnp.asarray(scheme.fused_encoder_matrix(), jnp.float32)
            q, mode = self._mea.curve.q, self._mea.mode

            def _results(a, b, mat_out, mat_back):
                return encrypted_coded_matmul(
                    enc, scheme.fused_blocks(a), b, mat_out, mat_back,
                    q=q, mode=mode, force_kernel=scheme.use_kernel)

            fn = self._jit(_results, "anytime_results_real")
            self._fused_cache[key] = fn
            if len(self._fused_cache) > self._fused_cache_max:
                self._fused_cache.popitem(last=False)
        else:
            self._fused_cache.move_to_end(key)
        return fn

    def _anytime_curve_fn(self, a_shape, b_shape, dtype, with_ref: bool):
        """Jitted stage 2: EVERY responder prefix decoded in one batched
        ``kernels.ops.prefix_decode`` contraction, plus the embedded-pair
        error proxy (and, for curve reporting, true relative errors
        against an in-trace A@B reference).  The per-round weight stacks
        are runtime arguments — straggler churn never recompiles."""
        key = ("any_curve", self._scheme_token, with_ref, a_shape, b_shape,
               dtype)
        fn = self._fused_cache.get(key)
        if fn is None:
            scheme = self.scheme
            m, n_out = a_shape[0], b_shape[-1]

            def _curve(results, w_lo, w_hi, valid, a, b):
                from ..kernels.ops import prefix_decode
                e = w_lo.shape[0]
                dec = prefix_decode(jnp.concatenate([w_lo, w_hi], axis=0),
                                    results, force_kernel=scheme.use_kernel)
                recon = jax.vmap(
                    lambda d: scheme.reconstruct_matmul(d, m, n_out))
                prod = recon(dec[:e])                       # (E, m, n_out)
                prod_hi = recon(dec[e:])
                diff = jnp.linalg.norm(
                    (prod - prod_hi).reshape(e, -1), axis=-1)
                den = jnp.linalg.norm(prod_hi.reshape(e, -1), axis=-1)
                prox = jnp.where(valid > 0, diff / jnp.maximum(den, 1e-12),
                                 jnp.inf)
                if not with_ref:
                    return prod, prox
                ref = jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
                rel = (jnp.linalg.norm((prod - ref[None]).reshape(e, -1),
                                       axis=-1) /
                       jnp.maximum(jnp.linalg.norm(ref), 1e-12))
                return prod, prox, rel

            fn = self._jit(_curve, "anytime_curve")
            self._fused_cache[key] = fn
            if len(self._fused_cache) > self._fused_cache_max:
                self._fused_cache.popitem(last=False)
        else:
            self._fused_cache.move_to_end(key)
        return fn

    def _prefix_weight_stacks(self, events):
        """Host-side per-prefix decode weights for one round's arrival
        order: (w_lo, ready, w_hi, valid).  Rateless schemes supply a
        genuine embedded pair (Berrut + Floater–Hormann at the WaitSpec's
        ``fh_degree``); threshold schemes have no second decoder — w_hi
        repeats w_lo with ``valid=0`` so the proxy reports inf below/at
        threshold (their per-prefix error is 0-or-undecodable anyway)."""
        order = [e.worker for e in events]
        w_lo, ready = self.scheme.prefix_decode_weights(order)
        pw = self.scheme.anytime_proxy_weights(order,
                                               fh_degree=self.fh_degree) \
            if hasattr(self.scheme, "anytime_proxy_weights") else None
        if pw is None:
            w_hi, valid = w_lo, np.zeros(len(order), np.float32)
        else:
            w_hi, valid = pw[0], np.asarray(pw[1], np.float32)
        return (jnp.asarray(w_lo), np.asarray(ready, bool),
                jnp.asarray(w_hi), jnp.asarray(valid))

    def _prefix_postprocess(self, ready, prox, valid):
        """Shared proxy cleanup: not-ready prefixes are inf; threshold
        schemes (no embedded pair anywhere) are exact once decodable."""
        prox = np.where(ready, np.asarray(prox, np.float64), np.inf)
        if not np.asarray(valid).any():
            prox = np.where(ready, 0.0, np.inf)
        return prox

    def _anytime_prefix_eval(self, a, b, round_idx: int, with_ref: bool):
        """The shared 2-dispatch prefix pipeline behind ErrorTarget rounds
        and ``anytime_curve``: stage 1 (encode + all worker matmuls),
        stage 2 (every prefix decoded + embedded-pair proxies, optionally
        true errors against an in-trace reference).

        Returns (events, ready, proxies, products, rel_errs-or-None).
        """
        _, t_comp = self._round_compute_time(a.shape, b.shape)
        events = virtual_events(self.straggler.delays(round_idx), t_comp)
        w_lo, ready, w_hi, valid = self._prefix_weight_stacks(events)
        self.dispatch_count += 1
        results = self._anytime_results_fn(a.shape, b.shape,
                                           str(a.dtype))(a, b)
        self.dispatch_count += 1
        out = self._anytime_curve_fn(a.shape, b.shape, str(a.dtype),
                                     with_ref=with_ref)(
            results, w_lo, w_hi, valid, a, b)
        prod, prox = out[0], out[1]
        rel = out[2] if with_ref else None
        prox = self._prefix_postprocess(ready, prox, valid)
        return events, ready, prox, prod, rel

    def _matmul_anytime(self, a: jnp.ndarray, b: jnp.ndarray, round_idx: int):
        """The proxy-driven round (ErrorTarget): run all workers' math,
        decode every prefix in one batched dispatch, stop at the earliest
        prefix whose embedded error estimate meets the target.  Two jitted
        dispatches per round, both LRU-cached per shape class."""
        blk, _ = self._round_compute_time(a.shape, b.shape)
        t0 = time.perf_counter()
        events, ready, prox, prod, _ = self._anytime_prefix_eval(
            a, b, round_idx, with_ref=False)
        stop = self._proxy_stop(events, prox)
        out = np.asarray(prod[stop - 1])
        jax.block_until_ready(out)
        t_master = time.perf_counter() - t0
        wait_s = float(events[stop - 1].t)
        crypto_s = self._crypto_overhead_elems(self.n * blk * a.shape[1],
                                               np.float32)
        hideable = (0.0 if self._pipeline is None else
                    min(t_master, self._encode_only_time(a.shape)))
        stats = self._stats(events, wait_s, encode_s=t_master,
                            compute_wait_s=wait_s, decode_s=0.0,
                            crypto_s=crypto_s, n_waited=stop, dispatches=2,
                            pipelined_s=self._account_encode(hideable,
                                                             wait_s))
        return out, stats

    def _matmul_anytime_real_fused(self, a: jnp.ndarray, b: jnp.ndarray,
                                   round_idx: int):
        """The encrypted anytime round as TWO dispatches: stage 1 is the
        one-dispatch encrypted pipeline (encode + wire-out + all worker
        matmuls + wire-back, :meth:`_anytime_results_real_fn`), stage 2
        the usual batched prefix decode + embedded-pair proxies.  The
        bits-codec wire is lossless, so proxies, stop index and output are
        bit-identical to the plain anytime round; ``crypto_s`` is
        attributed from the fused timeline (:meth:`_fused_crypto_time`)."""
        blk, t_comp = self._round_compute_time(a.shape, b.shape)
        events = virtual_events(self.straggler.delays(round_idx), t_comp)
        mat_out, mat_back = self._fused_mask_material()
        d0 = self.dispatch_count
        t0 = time.perf_counter()
        self.dispatch_count += 1
        results = self._anytime_results_real_fn(a.shape, b.shape,
                                                str(a.dtype))(
            a, b, jnp.asarray(mat_out), jnp.asarray(mat_back))
        w_lo, ready, w_hi, valid = self._prefix_weight_stacks(events)
        self.dispatch_count += 1
        prod, prox = self._anytime_curve_fn(a.shape, b.shape, str(a.dtype),
                                            with_ref=False)(
            results, w_lo, w_hi, valid, a, b)
        prox = self._prefix_postprocess(ready, prox, valid)
        stop = self._proxy_stop(events, prox)
        out = np.asarray(prod[stop - 1])
        jax.block_until_ready(out)
        t_master = time.perf_counter() - t0
        crypto_s = min(self._fused_crypto_time(blk, a.shape[1], b.shape[-1]),
                       t_master)
        modeled = self._crypto_overhead_elems(self.n * blk * a.shape[1],
                                              np.float32)
        wait_s = float(events[stop - 1].t)
        encode_s = t_master - crypto_s
        hideable = (0.0 if self._pipeline is None else
                    min(encode_s, self._encode_only_time(a.shape)))
        stats = self._stats(events, wait_s, encode_s=encode_s,
                            compute_wait_s=wait_s, decode_s=0.0,
                            crypto_s=crypto_s, n_waited=stop,
                            crypto_modeled_s=modeled,
                            dispatches=self.dispatch_count - d0,
                            pipelined_s=self._account_encode(hideable,
                                                             wait_s))
        return out, stats

    def _matmul_anytime_real(self, a: jnp.ndarray, b: jnp.ndarray,
                             round_idx: int):
        """The proxy-driven round over genuine ciphertexts: the 2-dispatch
        anytime pipeline split at its wire boundaries.

        Stage 1 becomes encode → MEA-ECC wire-out (all N shards) → batched
        worker matmul; stage 2 (the batched prefix decode + embedded-pair
        proxies) picks the stop prefix, and the consumed arrivals' results
        cross the wire back.  The bits codec is lossless, so proxies, stop
        index and output are bit-identical to the unencrypted anytime
        round.  ``crypto_s`` is the *measured* wire cost of what the
        master actually consumed: all N shards out, plus the results of
        the arrivals up to the stop prefix (stragglers past the stop never
        transmit).
        """
        blk, t_comp = self._round_compute_time(a.shape, b.shape)
        enc_fn, worker_fn, _ = self._staged_fns(a.shape, b.shape,
                                                str(a.dtype))
        events = virtual_events(self.straggler.delays(round_idx), t_comp)
        d0 = self.dispatch_count
        results, t_enc, crypto_out_s = self._staged_stage1(a, b, enc_fn,
                                                           worker_fn)
        # stage 2: batched prefix decode + proxies.  The bits-codec wire is
        # lossless, so running it on the pre-wire results is bit-identical
        # to decrypting first — which lets the stop prefix be computed
        # BEFORE the wire-back, and only the arrivals the policy actually
        # consumed pay (and charge) the return transfer.
        t0 = time.perf_counter()
        w_lo, ready, w_hi, valid = self._prefix_weight_stacks(events)
        self.dispatch_count += 1
        prod, prox = self._anytime_curve_fn(a.shape, b.shape, str(a.dtype),
                                            with_ref=False)(
            jnp.asarray(results), w_lo, w_hi, valid, a, b)
        prox = self._prefix_postprocess(ready, prox, valid)
        stop = self._proxy_stop(events, prox)
        out = np.asarray(prod[stop - 1])
        jax.block_until_ready(out)
        t_dec = time.perf_counter() - t0
        # wire back the consumed arrivals (decrypt-overwrite is the
        # identity on these bits; the measured time is the real cost)
        t0 = time.perf_counter()
        for ev in events[:stop]:
            results[ev.worker] = self._wire(results[ev.worker],
                                            self._worker_kps[ev.worker],
                                            self._master_kp)
        crypto_back_s = time.perf_counter() - t0
        wait_s = float(events[stop - 1].t)
        crypto_s = crypto_out_s + crypto_back_s
        modeled = self._crypto_overhead_elems(self.n * blk * a.shape[1],
                                              np.float32)
        hideable = (0.0 if self._pipeline is None else
                    min(t_enc, self._encode_only_time(a.shape)))
        stats = self._stats(events, wait_s, encode_s=t_enc,
                            compute_wait_s=wait_s, decode_s=t_dec,
                            crypto_s=crypto_s, n_waited=stop,
                            crypto_modeled_s=modeled,
                            dispatches=self.dispatch_count - d0,
                            pipelined_s=self._account_encode(hideable,
                                                             wait_s))
        return out, stats

    def anytime_curve(self, a: np.ndarray, b: np.ndarray, round_idx: int = 0):
        """The full error-vs-latency curve of one virtual-clock round:
        for every arrival prefix, the virtual time and the decode's true
        relative error (inf where the scheme can't decode yet), plus the
        in-trace embedded-pair proxy and the monotone ``best_err``
        envelope.  Whole-curve cost: TWO jitted dispatches per shape class
        (stage 1 worker results + stage 2 batched prefix decode), however
        many error points the round has.

        Returns a list of :class:`repro.runtime.scheduler.AnytimePoint`.
        """
        if not getattr(self.scheme, "supports_fused", False):
            raise NotImplementedError(
                f"{self.name!r}: anytime curves need a linear data-coded "
                "scheme (prefix decode stacks)")
        a = jnp.asarray(a, jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        events, ready, prox, _, rel = self._anytime_prefix_eval(
            a, b, round_idx, with_ref=True)
        return assemble_curve(events, np.asarray(rel, np.float64), ready,
                              prox)

    # ------------------------------------------------------------ adaptive
    def _build_candidate_scheme(self, **overrides):
        """Registry-backed scheme construction for the adaptive
        controller's candidates: the spec's own build, with ``k_blocks``
        (or a scheme-specific knob like GLCC's ``n_groups``) overridden."""
        from ..core import registry
        code = self.spec.code
        kwargs = dict(n_workers=code.n_workers, k_blocks=code.k_blocks,
                      t_colluding=self.spec.privacy.t_colluding,
                      noise_scale=self.spec.privacy.noise_scale,
                      seed=self.spec.seed, use_kernel=code.use_kernel,
                      **dict(code.extra))
        kwargs.update(overrides)
        return registry.build(code.scheme, **kwargs)

    def _adaptive_retune(self, round_idx: int) -> None:
        """Apply the controller's decision (if one is due) BEFORE the
        round runs: swap scheme / wait policy / fh_degree.  The swapped
        scheme's compiled functions live under its own cache token, so
        redispatch is recompile-free once each (candidate, shape) pair
        has been traced."""
        dec = self.adaptive.maybe_decide(round_idx, health=self.health)
        if dec is None:
            return
        scheme = self.adaptive.scheme_for(dec)
        if scheme is not self.scheme:
            self.scheme = scheme
            self.k = int(dec.k_blocks)
            self._scheme_token = self.adaptive._key(dec.overrides)
            supports = bool(getattr(scheme, "supports_fused", False))
            stable = bool(getattr(scheme, "fused_decode_stable", False))
            fused = self.spec.code.fused
            self.use_fused = (supports and stable) if fused is None \
                else bool(fused)
            if self.spec.transport.backend != "virtual" or self.fault.active:
                self.use_fused = False
        self.policy = self.adaptive.policy_for(dec)
        self.fh_degree = dec.fh_degree
        self.wait_for = self.scheme.wait_policy(self.straggler.n_stragglers)

    def _adaptive_observe(self, round_idx: int, stats: RoundStats,
                          shapes) -> None:
        """Feed the round's consumed arrivals back to the estimator and
        the health tracker.  Only the consumed prefix is observed — the
        real transports never see past what the policy waited for, so
        observing the virtual clock's full timeline would make the two
        transports fit different models from the same trace.  The first
        round of a scheme at given shapes compiles its worker programs,
        and on real transports that compile lands in the arrival times:
        its baseline is no compute measurement, so no transport feeds
        it."""
        consumed = tuple(stats.arrivals[: max(stats.n_waited, 1)])
        key = (self._scheme_token,) + tuple(shapes)
        timed = key in self._timed_rounds
        self._timed_rounds.add(key)
        self.adaptive.observe(round_idx, consumed,
                              k_blocks=int(getattr(self.scheme, "k_blocks",
                                                   self.k)) if timed else None)
        if self.health is not None and not self.fault.active:
            for t, w in consumed:
                self.health.record_ok(int(w), float(t))

    # --------------------------------------------------------------- rounds
    def matmul(self, a: np.ndarray, b: np.ndarray, round_idx: int = 0):
        """Returns (result (m, n), RoundStats).  Result stacked over K blocks
        for block schemes, reshaped to a's row layout.

        On the fused path encode/compute/decode are one dispatch, so the
        whole master-side wall time is reported as ``encode_s`` and
        ``decode_s`` is 0; ``compute_wait_s`` stays the virtual-clock wait.

        Under ``AdaptiveSpec(policy="adaptive")`` each round is bracketed
        by the controller: retune (maybe) before, observe arrivals after
        — the round itself runs the unchanged engine paths.
        """
        with span("round"):
            if self.adaptive is not None:
                self._adaptive_retune(round_idx)
                out, stats = self._matmul_inner(a, b, round_idx)
                self._adaptive_observe(round_idx, stats,
                                       (np.shape(a), np.shape(b)))
                return out, stats
            return self._matmul_inner(a, b, round_idx)

    def _matmul_inner(self, a: np.ndarray, b: np.ndarray, round_idx: int = 0):
        a = jnp.asarray(a, jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        real = self.encrypt == "real"
        if self.fault.active:
            return self._matmul_faulted(a, b, round_idx)
        if self.use_fused:
            if self.policy.needs_proxy:
                if real:
                    if self._crypto_fused:
                        return self._matmul_anytime_real_fused(a, b,
                                                               round_idx)
                    return self._matmul_anytime_real(a, b, round_idx)
                return self._matmul_anytime(a, b, round_idx)
            if real:
                if self._crypto_fused:
                    return self._matmul_real_fused(a, b, round_idx)
                return self._matmul_real(a, b, round_idx)
            return self._matmul_fused(a, b, round_idx)
        t0 = time.perf_counter()
        # the round's work is a picklable task object (runtime.tasks), the
        # SAME object on every backend — in-process rounds call it
        # directly, the socket mesh ships it to worker processes; the
        # math runs through jnp either way, so the bits cannot diverge
        if self.scheme.pair_coded:
            ea, eb = self.scheme.encode_pair(a, b)
            jax.block_until_ready((ea, eb))
            shards = [(ea[i], eb[i]) for i in range(self.n)]
            f = PairMatmulTask()
            lhs_shape, rhs_shape = ea.shape[1:], eb.shape[1:]
        else:
            enc = self.scheme.encode(a)
            jax.block_until_ready(enc)
            shards = [np.asarray(enc[i]) for i in range(self.n)]
            f = MatmulTask(b)
            lhs_shape, rhs_shape = enc.shape[1:], b.shape
        t_enc = time.perf_counter() - t0
        if self.pool.backend == "socket" and self.scheme.pair_coded:
            # pair shards cross a process boundary: host arrays on the wire
            shards = [(np.asarray(sa), np.asarray(sb)) for sa, sb in shards]

        crypto_s = 0.0
        plain_shards = shards       # shapes for the modeled-crypto estimate
        sealed = real and self.pool.backend == "socket"
        if real and not sealed:
            # in-process wire: every worker decrypts bit-identical shard
            # bytes, round-tripped master-side
            t0 = time.perf_counter()
            shards = [
                tuple(self._wire(part, self._master_kp, self._worker_kps[i])
                      for part in s) if isinstance(s, tuple)
                else self._wire(s, self._master_kp, self._worker_kps[i])
                for i, s in enumerate(shards)]
            crypto_s += time.perf_counter() - t0
        elif sealed:
            # socket wire: shards leave the master SEALED — genuine
            # MEA-ECC ciphertext limbs cross the socket (zero re-encode,
            # see runtime.wire), the worker process decrypts, multiplies,
            # and encrypts the product back under a dispatch-time nonce
            t0 = time.perf_counter()
            f = SealedMatmulTask(self._mea, self._worker_kps,
                                 self._master_kp.pk,
                                 b=None if self.scheme.pair_coded
                                 else np.asarray(b))
            shards = [
                (i,
                 tuple(self._mea.encrypt(np.asarray(part),
                                         self._worker_kps[i].pk,
                                         sender=self._master_kp,
                                         nonce=next(self._nonce))
                       for part in (s if isinstance(s, tuple) else (s,))),
                 next(self._nonce))          # the worker's reply nonce
                for i, s in enumerate(shards)]
            self.dispatch_count += self.n       # one encrypt core each
            crypto_s += time.perf_counter() - t0

        t_comp = self._worker_compute_time(lhs_shape, rhs_shape)
        resp, results, wait_s, plan = self._loop_round(shards, f, round_idx,
                                                       t_comp)
        if sealed:
            # responders' products arrive as ciphertext to the master key
            t0 = time.perf_counter()
            results = [np.asarray(self._mea.decrypt(ct, self._master_kp))
                       for ct in results]
            self.dispatch_count += len(results)
            crypto_s += time.perf_counter() - t0
        elif real:
            # wire back: responders encrypt their products to the master
            t0 = time.perf_counter()
            results = [self._wire(r, self._worker_kps[i], self._master_kp)
                       for i, r in zip(resp, results)]
            crypto_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        dec = self.scheme.decode(jnp.asarray(np.stack(results)), list(resp))
        out = np.asarray(self.scheme.reconstruct_matmul(dec, a.shape[0],
                                                        b.shape[-1]))
        t_dec = time.perf_counter() - t0
        modeled = self._crypto_overhead(plain_shards)
        stats = RoundStats(t_enc, wait_s, t_dec,
                           crypto_s if real else modeled, len(resp),
                           crypto_modeled_s=modeled if real else 0.0,
                           policy=self.policy.name,
                           arrivals=tuple((e.t, e.worker)
                                          for e in plan) if plan else (),
                           decode_at_s=wait_s,
                           pipelined_s=self._account_encode(t_enc, wait_s))
        return out, stats

    # ------------------------------------------------- fault-tolerant path
    def _fault_policy_target(self) -> int:
        """Clean-responder count the defended round drives toward (the
        count-based policies' target; Deadline rounds are budget-bounded
        instead and only need the scheme's minimum decodable prefix)."""
        min_ready = scheme_min_responders(self.scheme)
        ctx = RoundContext(scheme=self.scheme,
                           n_stragglers=self.straggler.n_stragglers,
                           events=[], min_ready=min_ready)
        try:
            tgt = int(self.policy.target(ctx))
        except NotImplementedError:
            tgt = min_ready
        return max(min(tgt, self.n), min_ready)

    def _degraded_rel_err(self, slots, stack) -> Optional[float]:
        """Embedded-pair estimate of a degraded decode's error: the
        disagreement between the scheme's decode and its higher-order
        proxy decode over the surviving slots (rateless schemes; None
        when the pair is unavailable at this prefix)."""
        order = list(slots)
        proxy = getattr(self.scheme, "anytime_proxy_weights", None)
        if proxy is None:
            return None
        hi = proxy(order, fh_degree=self.fh_degree)
        if hi is None:
            return None
        w_lo, ready = self.scheme.prefix_decode_weights(order)
        if not bool(np.asarray(hi[1])[-1]) or not bool(np.asarray(ready)[-1]):
            return None
        full = np.zeros((self.n, int(np.prod(stack.shape[1:]))), np.float64)
        for i, s in enumerate(order):
            full[s] = np.asarray(stack[i], np.float64).reshape(-1)
        lo_d = np.asarray(w_lo[-1], np.float64) @ full
        hi_d = np.asarray(hi[0][-1], np.float64) @ full
        den = max(float(np.linalg.norm(hi_d)), 1e-12)
        return float(np.linalg.norm(lo_d - hi_d) / den)

    def _matmul_faulted(self, a: jnp.ndarray, b: jnp.ndarray,
                        round_idx: int):
        """The fault round: injected faults (via the wrapping transport)
        and/or engine-side defenses (``FaultSpec.handle``).

        Work travels in ``(worker, slot, payload)`` envelopes — slot s is
        encoder row s, so a re-dispatch hands the SAME coded shard to a
        different worker and the decode stays slot-indexed.  Defended
        rounds drain arrivals, screen the accumulated clean set with
        leave-one-out residuals (corrupted responders' mask bits are
        cleared, their producers recorded in ``WorkerHealth``), and
        re-dispatch missing slots to the healthiest workers with capped
        exponential backoff until the policy's target is met, the retry
        budget runs out, or no healthy workers remain.  Exhausted rateless
        rounds decode the surviving prefix (``degraded=True`` with the
        embedded-pair ``achieved_rel_err``); exhausted threshold rounds
        raise :class:`~repro.runtime.faults.DegradedRoundError` carrying
        the partial state.  Undefended rounds (injection only) dispatch
        once and decode whatever arrives — corrupt results included.
        """
        scheme, fault = self.scheme, self.fault
        real = self.encrypt == "real"
        handle_faults = fault.handle
        min_ready = scheme_min_responders(scheme)
        budget = getattr(self.policy, "t_budget", None)
        needed = min_ready if budget is not None else \
            self._fault_policy_target()

        t0 = time.perf_counter()
        enc = np.asarray(scheme.encode(a))            # (N, blk, d)
        self.dispatch_count += 1
        t_enc = time.perf_counter() - t0
        blk, t_comp = self._round_compute_time(a.shape, b.shape)
        n_out = int(b.shape[-1])
        crypto_s = 0.0
        transport, health = self._fault_transport, self.health

        # the envelope task is picklable (runtime.tasks) so the SAME
        # defended round runs on the socket mesh — the reply nonce is
        # drawn at dispatch and travels in the envelope, because a shared
        # nonce counter cannot cross a process boundary
        worker_fn = EnvelopeMatmulTask(
            b, mea=self._mea if real else None,
            worker_kps=self._worker_kps if real else None,
            master_pk=self._master_kp.pk if real else None)

        def dispatch(assign: dict, attempt: int):
            nonlocal crypto_s
            envs = [None] * self.n
            if real:
                tw = time.perf_counter()
                for w, slot in assign.items():
                    envs[w] = (w, slot, self._mea.encrypt(
                        enc[slot], self._worker_kps[w].pk,
                        sender=self._master_kp, nonce=next(self._nonce)),
                        next(self._nonce))
                self.dispatch_count += 2 * len(assign)
                crypto_s += time.perf_counter() - tw
            else:
                for w, slot in assign.items():
                    envs[w] = (w, slot, enc[slot])
            rid = retry_round_index(round_idx, attempt)
            return transport.submit_round(envs, worker_fn, rid,
                                          t_compute=t_comp, budget=budget,
                                          min_ready=min_ready)

        clean: dict = {}                   # slot -> (worker, result array)
        arrivals: list = []                # (cumulative t, worker)
        excluded_workers: list = []
        offenders: set = set()
        quarantined0 = tuple(health.quarantined(round_idx)) \
            if (handle_faults and health is not None) else ()
        wait_total, retries, attempt = 0.0, 0, 0
        # full-jitter backoff, seeded off the round's fault SeedSequence:
        # retries never thundering-herd, yet the trace stays reproducible
        backoff_rng = np.random.default_rng(np.random.SeedSequence(
            [int(self._fault_seed), int(round_idx), _BACKOFF_STREAM]))
        if handle_faults and health is not None:
            avail = [w for w in range(self.n)
                     if not health.is_quarantined(w, round_idx)]
        else:
            avail = list(range(self.n))
        assign = {w: w for w in avail}

        while True:
            handle = dispatch(assign, attempt)
            targets = set(assign)
            seen: set = set()
            observed_t = 0.0
            try:
                for ev in handle.events():
                    if ev.worker not in targets:
                        continue           # stray slot from an earlier plan
                    seen.add(ev.worker)
                    observed_t = max(observed_t, float(ev.t))
                    try:
                        slot, payload = handle.result(ev.worker)
                    except ResultDropped:
                        offenders.add(ev.worker)
                        if handle_faults and health is not None:
                            health.record_drop(ev.worker, round_idx)
                        continue
                    if real:
                        tw = time.perf_counter()
                        try:
                            arr = np.asarray(self._mea.decrypt(
                                payload, self._master_kp), np.float32)
                        except Exception:
                            # a tampered ciphertext that fails to decode at
                            # all is still a response — screening evicts
                            # the non-finite row before scoring
                            arr = np.full((blk, n_out), np.nan, np.float32)
                        self.dispatch_count += 2
                        crypto_s += time.perf_counter() - tw
                    else:
                        arr = np.asarray(payload, np.float32)
                    if arr.shape != (blk, n_out):
                        arr = np.full((blk, n_out), np.nan, np.float32)
                    clean[int(slot)] = (int(ev.worker), arr)
                    arrivals.append((wait_total + float(ev.t),
                                     int(ev.worker)))
                    if handle_faults and health is not None:
                        health.record_ok(ev.worker, float(ev.t))
                    if budget is None and len(clean) >= needed:
                        break
            finally:
                handle.finish()
            if handle_faults and fault.screen and clean:
                slots = sorted(clean)
                results_arr = np.zeros((self.n, blk, n_out), np.float32)
                mask = np.zeros(self.n, np.float32)
                for s in slots:
                    results_arr[s] = clean[s][1]
                    mask[s] = 1.0
                _, evicted, _ = screen_responders(
                    scheme, results_arr, mask,
                    threshold=fault.residual_threshold,
                    factor=fault.residual_factor,
                    norm_factor=fault.norm_factor,
                    max_exclude=max(0, len(slots) - min_ready))
                for s in evicted:
                    w = clean[s][0]
                    excluded_workers.append(w)
                    offenders.add(w)
                    if health is not None:
                        health.record_corrupt(w, round_idx)
                    del clean[s]
            if len(clean) >= needed:
                wait_total += observed_t
                break
            # target missed: charge what the master actually waited — the
            # deadline budget, or the per-worker timeout on the crashed
            # assignments (the stream exhausted without them)
            if budget is not None:
                wait_total += float(budget)
            else:
                timeout = (fault.worker_timeout_s
                           if fault.worker_timeout_s is not None
                           else fault.timeout_factor * max(observed_t,
                                                           t_comp))
                wait_total += max(observed_t, timeout)
                if handle_faults and health is not None:
                    for w in sorted(targets - seen):
                        offenders.add(w)
                        health.record_crash(w, round_idx)
            attempt += 1
            if not handle_faults or attempt > fault.max_retries:
                break
            missing = [s for s in range(self.n) if s not in clean]
            cands = (health.ranked(round_idx, exclude=offenders)
                     if health is not None else
                     [w for w in range(self.n) if w not in offenders])
            if not cands:
                break
            wait_total += retry_backoff(attempt, fault.backoff_s,
                                        fault.backoff_cap_s,
                                        rng=backoff_rng)
            retries += 1
            assign = dict(zip(cands, missing))

        slots = sorted(clean)
        degraded = len(clean) < needed
        achieved = None
        if degraded:
            stack = (np.stack([clean[s][1] for s in slots])
                     if slots else None)
            if not slots or len(slots) < min_ready:
                raise DegradedRoundError(
                    f"round {round_idx}: {len(slots)} clean result(s) "
                    f"after {retries} re-dispatch(es), scheme needs "
                    f"{min_ready} (policy target {needed})",
                    clean_slots=slots, results=stack,
                    excluded=excluded_workers, retries=retries,
                    needed=needed)
            achieved = self._degraded_rel_err(slots, stack)
        t0 = time.perf_counter()
        stack = np.stack([clean[s][1] for s in slots])
        dec = scheme.decode(jnp.asarray(stack), list(slots))
        out = np.asarray(scheme.reconstruct_matmul(dec, a.shape[0],
                                                   b.shape[-1]))
        self.dispatch_count += 1
        t_dec = time.perf_counter() - t0
        modeled = self._crypto_overhead_elems(self.n * blk * a.shape[1],
                                              np.float32)
        stats = RoundStats(
            encode_s=t_enc, compute_wait_s=wait_total, decode_s=t_dec,
            crypto_s=crypto_s if real else modeled, n_waited=len(slots),
            crypto_modeled_s=modeled if real else 0.0,
            policy=self.policy.name, arrivals=tuple(arrivals),
            decode_at_s=wait_total,
            pipelined_s=self._account_encode(t_enc, wait_total),
            retries=retries, excluded=tuple(excluded_workers),
            quarantined=quarantined0, degraded=degraded,
            achieved_rel_err=achieved,
            decode_mask=tuple(1 if s in clean else 0
                              for s in range(self.n)))
        return out, stats

    def _loop_round(self, shards, f, round_idx: int, t_comp: float):
        """The unfused round's worker phase under the wait policy.

        Returns (responders, results_in_responder_order, wait_s, events).
        Virtual clock: the policy picks the prefix off the analytic
        timeline and ONLY the selected responders' work runs — except for
        proxy-driven policies, whose error proxy needs every arrival's
        result as it lands.  Real threads: the event loop in
        ``WorkerPool.run_round_real`` consumes completions until the
        policy is satisfied.
        """
        pool, policy, scheme = self.pool, self.policy, self.scheme
        if pool.real_threads:
            events, done, _ = pool.run_round_real(
                shards, f, round_idx, policy=policy, scheme=scheme,
                n_stragglers=self.straggler.n_stragglers)
            ctx = RoundContext(scheme=scheme,
                               n_stragglers=self.straggler.n_stragglers,
                               events=events,
                               min_ready=scheme_min_responders(scheme))
            stop = int(policy.stop_index(ctx))
            resp = np.sort(np.asarray([e.worker for e in events[:stop]],
                                      dtype=np.int64))
            return resp, [done[i] for i in resp], float(events[stop - 1].t), \
                events
        delays = self.straggler.delays(round_idx)
        proxy_fn = None
        results_all = None
        if policy.needs_proxy:
            # the proxy needs worker outputs: run everyone (this is the
            # oracle path; the fused anytime pipeline is the fast one)
            results_all = [f(s) for s in shards]
            fh_degree = self.fh_degree

            def proxy_fn(events):
                order = [e.worker for e in events]
                w_lo, ready = scheme.prefix_decode_weights(order)
                pw = scheme.anytime_proxy_weights(order,
                                                  fh_degree=fh_degree) \
                    if hasattr(scheme, "anytime_proxy_weights") else None
                stack = np.stack(results_all).reshape(len(results_all), -1)
                if pw is None:
                    return np.where(ready, 0.0, np.inf)
                w_hi, valid = pw
                lo = np.einsum("ekn,nf->ekf", np.asarray(w_lo, np.float64),
                               stack.astype(np.float64))
                hi = np.einsum("ekn,nf->ekf", np.asarray(w_hi, np.float64),
                               stack.astype(np.float64))
                num = np.linalg.norm((lo - hi).reshape(len(order), -1),
                                     axis=-1)
                den = np.linalg.norm(hi.reshape(len(order), -1), axis=-1)
                prox = np.where(valid, num / np.maximum(den, 1e-12), np.inf)
                return np.where(ready, prox, np.inf)

        plan = plan_round(scheme, policy, delays, t_comp,
                          self.straggler.n_stragglers, proxy_fn=proxy_fn)
        resp = plan.responders
        if results_all is not None:
            results = [results_all[i] for i in resp]
        else:
            results = [f(shards[i]) for i in resp]
        return resp, results, plan.wait_s, plan.events
