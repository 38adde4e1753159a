#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip, checked by the repo's own
means.

    python chip_smoke.py              # from the repo root, on a TPU host

Phase A — coded rounds through ``Session(ClusterSpec.paper_fig3()).matmul``
and ``.anytime_curve`` (SPACDC N=30, K=24, T=3, seven stragglers) at
A (18432×3584) float32 @ B (3584×512): a qwen2-7b FFN weight, its rows
(d_ff 18944) rounded down to a multiple of K, against a 512-column
activation batch.  Runs one plain fused round, one ``encrypt="real"``
round in ``paper`` and one in ``stream`` cipher mode, and one anytime
curve, all with the default kernel dispatch (compiled Pallas kernels on
TPU).  Checks: the kernel round against the same spec's XLA twin
(``use_kernel=False``) within ``KERNEL_RTOL``; each encrypted round equal
to the plain round bit for bit (the bits codec is lossless); the anytime
``best_err`` envelope never increasing.

Phase B — coded serving through ``Session.serve`` on qwen2-7b at its
published widths (d_model 3584, 28 query and 4 KV heads of 128, d_ff
18944), cut to one chip's share: ``SERVE_LAYERS`` layers and a quarter of
the vocabulary.  Run A serves four ragged requests (prompts of 16-32
tokens, 8 generated tokens each) under ``ClusterSpec.serve_deadline()``
with every projection coded (``coded_layers="all"``), one dispatch per
step.  Run B serves the same four requests under the exact MDS spec of
``tests/test_serve.py`` coded and uncoded, in one session, at compute
float32 and full matmul precision, and compares every slot's logits at
every step within ``LOGIT_RTOL``.

Every line but the last reports one phase as JSON: wall seconds on the
host clock up to ``block_until_ready`` (first call, compile included, and
a steady repeat where the phase has one), lowering and compile seconds
from JAX's own compile events, ``bytes_in_use`` and ``peak_bytes_in_use``
so far, and the ``tpu_custom_call`` ops of each program the phase
compiled.  Serving's virtual-clock fields
(requests/s, step percentiles, decode times) are model outputs and are
not printed.  The last line is ``{"ok": true, "device": {...}}``, printed
only when every phase ran and every check passed.  With no TPU the script
exits 1 before any phase.  Everything runs in this one process: the chip
belongs to one process at a time.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import re
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))   # the repo is not an installed package

ROUND_SHAPE = (18432, 3584, 512)        # A rows, d, B columns
# A qwen2-7b layer holds 0.93 GB of f32 weights and 1.87 GB of coded
# shards (N/K = 2x); the quarter vocabulary 2.2 GB (embed, unembed and its
# shards).  Three layers (~10.6 GB by that count) did not fit a v5e: the
# unembed's encode found 0.48 GB free when it needed 1.01 GB.  Two layers
# are the deepest cut that runs, and main() checks 2 GiB stay free.
SERVE_LAYERS = 2
SERVE_VOCAB = 152064 // 4
GIB = 2 ** 30

# Kernel round vs XLA twin: the same f32 contractions at full precision,
# accumulated in tiles (encode over J=27 blocks, worker matmul over d=3584
# in steps of 256) where XLA's dot picks its own order.  Reordered f32 sums
# of d terms differ by ~eps32·sqrt(d) ≈ 7e-6 of their scale; 1e-4 leaves
# a margin, while one bf16 pass (2^-9) would fail it.
KERNEL_RTOL = 1e-4
# Coded vs uncoded logits, both arms at compute float32 and full matmul
# precision: what differs is the f32 shard matmuls and the f32 inverse of
# a K=4 Vandermonde system (condition ~10), ~1e-6 of the logit scale at
# the tiny config and 1.7e-6 at this chip share on a v5e.  A mis-weighted
# decode is off by O(1) of the scale; the same v5e run with the shard
# matmuls at default precision (bf16 operands) was off by 1.0e-2.  Both
# fall far outside the bound.  The same bound as tests/test_serve.py.
LOGIT_RTOL = 1e-4


class CheckFailed(Exception):
    """A correctness check of a phase failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


# lowering and XLA compile; tracing is left out because its events nest
# (an inner jit's trace is timed inside its caller's) and would count twice
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_DUMP_NAME = re.compile(r"jax_ir\d+_(.+)_compile\.mlir$")


class Probe:
    """Per-phase measurements: host wall time, compile seconds (lowering
    and XLA compile, summed from JAX's compile-duration events), device
    memory in use and its peak, and the ``tpu_custom_call`` ops of every
    program compiled in the phase (read from the StableHLO JAX dumps for
    each compile — the dump happens before the persistent-cache lookup,
    so a warm cache still reports)."""

    def __init__(self):
        import jax
        self._jax = jax
        self._compile_s = 0.0
        self.records = {}

    def __enter__(self):
        jax = self._jax
        self._tmp = tempfile.TemporaryDirectory()
        self._saved = (jax.config.read("jax_dump_ir_to"),
                       jax.config.read("jax_dump_ir_modes"))
        jax.config.update("jax_dump_ir_to", self._tmp.name)
        jax.config.update("jax_dump_ir_modes", "stablehlo")
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax = self._jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        jax.config.update("jax_dump_ir_to", self._saved[0])
        jax.config.update("jax_dump_ir_modes", self._saved[1])
        self._tmp.cleanup()
        return False

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self._compile_s += duration

    def _dumps(self):
        return set(Path(self._tmp.name).iterdir())

    @contextlib.contextmanager
    def phase(self, name: str):
        rec = {"phase": name}
        before, c0 = self._dumps(), self._compile_s
        t0 = time.perf_counter()
        yield rec
        rec["wall_s"] = time.perf_counter() - t0
        rec["compile_s"] = self._compile_s - c0
        kernels = {}
        for path in sorted(self._dumps() - before):
            m = _DUMP_NAME.search(path.name)
            if m:
                n = path.read_text().count("@tpu_custom_call")
                if n:
                    kernels.setdefault(m.group(1), []).append(n)
        rec["tpu_custom_call"] = kernels
        stats = self._jax.devices()[0].memory_stats() or {}
        rec["bytes_in_use"] = stats.get("bytes_in_use")
        rec["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        rec["bytes_limit"] = stats.get("bytes_limit")
        self.records[name] = rec
        print("phase " + json.dumps(rec), flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# Phase A: coded rounds
# --------------------------------------------------------------------------

def phase_rounds(probe: Probe, rows: int, d: int, n_out: int, seed: int = 0):
    """Plain fused, encrypted paper/stream and anytime rounds of the
    paper's fig-3 spec at A (rows×d) @ B (d×n_out), checked."""
    from repro.api import ClusterSpec, CryptoSpec, Session
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((rows, d), dtype=np.float32)
    b = rng.standard_normal((d, n_out), dtype=np.float32)
    spec = ClusterSpec.paper_fig3()
    # Session.matmul returns host arrays, so every timed call has waited
    # for the device (block_until_ready) and copied the result back

    with probe.phase("A.fused_round") as rec:
        with Session(spec) as s:
            plain, _ = s.matmul(a, b, round_idx=0)
            _, rec["steady_wall_s"] = _timed(lambda: s.matmul(a, b,
                                                              round_idx=0))
    with probe.phase("A.xla_twin") as rec:
        twin_spec = dataclasses.replace(
            spec, code=dataclasses.replace(spec.code, use_kernel=False))
        with Session(twin_spec) as s:
            twin, _ = s.matmul(a, b, round_idx=0)
        scale = float(np.abs(twin).max())
        rec["max_abs_diff_vs_kernel"] = float(np.abs(plain - twin).max())
        rec["scale"] = scale
    check(np.isfinite(plain).all(), "fused round output is not finite")
    check(rec["max_abs_diff_vs_kernel"] <= KERNEL_RTOL * scale,
          f"kernel round vs XLA twin: {rec['max_abs_diff_vs_kernel']} > "
          f"{KERNEL_RTOL} * {scale}")

    for mode in ("paper", "stream"):
        with probe.phase(f"A.encrypted_{mode}") as rec:
            real = dataclasses.replace(
                spec, crypto=CryptoSpec(encrypt="real", cipher_mode=mode))
            with Session(real) as s:
                enc, _ = s.matmul(a, b, round_idx=0)
                _, rec["steady_wall_s"] = _timed(lambda: s.matmul(
                    a, b, round_idx=0))
            rec["bit_identical_to_plain"] = bool(np.array_equal(enc, plain))
        check(rec["bit_identical_to_plain"],
              f"encrypted {mode} round differs from the plain round")

    with probe.phase("A.anytime_curve") as rec:
        with Session(spec) as s:
            curve = s.anytime_curve(a, b)
        best = np.asarray([p.best_err for p in curve])
        rec["points"] = len(curve)
        rec["final_best_err"] = float(best[-1])
    check(bool(np.all(np.diff(best) <= 0)), "anytime best_err increased")
    check(np.isfinite(best[-1]), "anytime curve never decoded")


# --------------------------------------------------------------------------
# Phase B: coded serving
# --------------------------------------------------------------------------

def chip_share_config(n_layers: int = SERVE_LAYERS,
                      vocab: int = SERVE_VOCAB):
    """qwen2-7b at its published widths, cut to one chip's share: the
    first ``n_layers`` layers (the rest would be further pipeline stages)
    and a ``vocab``-id slice of the vocabulary (the unembed split over
    chips); traffic draws its ids from the slice."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("qwen2-7b"), n_layers=n_layers,
                               vocab_size=vocab)


def exact_spec(max_slots: int):
    """The parity spec of tests/test_serve.py: MDS N=8/K=4, wait for all
    8, no stragglers — exact decode in exact arithmetic."""
    from repro.api import (ClusterSpec, CodeSpec, ServeSpec, StragglerSpec,
                           WaitSpec)
    return ClusterSpec(code=CodeSpec(scheme="mds", n_workers=8, k_blocks=4),
                       wait=WaitSpec(policy="first_k", k=8),
                       straggler=StragglerSpec(n_stragglers=0),
                       serve=ServeSpec(coded_layers="all",
                                       max_slots=max_slots))


def smoke_requests(cfg, n_requests: int = 4, prompt_len=(16, 32),
                   gen: int = 8, seed: int = 0):
    """Ragged requests, all arriving at t=0, with ids from ``cfg``'s
    vocabulary."""
    from repro.runtime.serve_loop import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(
                1, cfg.vocab_size, int(rng.integers(prompt_len[0],
                                                    prompt_len[1] + 1))
            ).astype(np.int32), gen=gen)
            for i in range(n_requests)]


def exact_logit_gap(cfg, requests, seed: int = 0) -> dict:
    """Serve ``requests`` under :func:`exact_spec` with every projection
    coded and uncoded, in one session (same weights), both at compute
    float32 and full matmul precision, recording each step's logits;
    returns :func:`repro.runtime.serve_loop.logit_gap` of the two."""
    import jax
    from repro.api import Session
    from repro.runtime.serve_loop import logit_gap
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")
    with jax.default_matmul_precision("highest"), \
            Session(exact_spec(len(requests))) as s:
        coded = s.serve(cfg32, requests=requests, seed=seed,
                        check_agreement=False, record_logits=True)
        plain = s.batcher(cfg32, seed=seed, coded_layers="none").run(
            requests, record_logits=True)
        gap = logit_gap(coded.requests, plain.requests)
        gap["finite"] = all(bool(np.isfinite(r.logits).all())
                            for r in coded.requests)
    return gap


def phase_serve(probe: Probe, cfg, *, n_requests: int = 4,
                prompt_len=(16, 32), gen: int = 8, seed: int = 0):
    """Coded serving of ``cfg`` through ``Session.serve`` (run A) and the
    coded-vs-uncoded logit comparison of the exact spec (run B)."""
    from repro.api import ClusterSpec, Session
    print("cut " + json.dumps({
        "model": cfg.name, "n_layers": cfg.n_layers, "d_model": cfg.d_model,
        "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim_, "d_ff": cfg.d_ff,
        "vocab_size": cfg.vocab_size, "param_dtype": cfg.param_dtype,
        "compute_dtype": cfg.compute_dtype}), flush=True)
    reqs = smoke_requests(cfg, n_requests, prompt_len, gen, seed)

    with probe.phase("B.serve_deadline_all") as rec:
        spec = ClusterSpec.serve_deadline(coded_layers="all",
                                          max_slots=n_requests)
        with Session(spec) as s:
            rep = s.serve(cfg, requests=reqs, seed=seed,
                          check_agreement=False)
        rec.update(mode=rep.mode, steps=len(rep.step_stats),
                   generated=int(sum(len(r.tokens) for r in rep.requests)),
                   step_compiles=rep.trace_count,
                   busy_wall_s=rep.busy_wall_s,
                   tokens_per_busy_wall_s=rep.tok_s)
    check(rep.mode == "instep", f"serve ran in {rep.mode!r} mode")
    check(len(rep.requests) == n_requests, "not every request was served")
    for r in rep.requests:
        check(len(r.tokens) == gen, f"request {r.rid}: {len(r.tokens)} tokens")
        check(bool(((r.tokens >= 0) & (r.tokens < cfg.vocab_size)).all()),
              f"request {r.rid}: token ids out of range")
    del rep, s
    gc.collect()                     # release run A's weights and shards

    with probe.phase("B.exact_logits") as rec:
        rec.update(exact_logit_gap(cfg, reqs, seed))
    gc.collect()
    check(rec["finite"], "coded logits are not finite")
    check(rec["rows"] >= sum(len(r.prompt) for r in reqs),
          "a request's prefill was not compared")
    check(rec["max_abs_diff"] <= LOGIT_RTOL * rec["scale"],
          f"coded vs uncoded logits: {rec['max_abs_diff']} > "
          f"{LOGIT_RTOL} * {rec['scale']}")


# --------------------------------------------------------------------------

KERNEL_PROGRAMS = {"A.fused_round": "jit__round",
                   "A.encrypted_paper": "jit__round",
                   "A.encrypted_stream": "jit__round",
                   "B.serve_deadline_all": "jit_step"}


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print("compile_cache " + json.dumps(enable_compile_cache()), flush=True)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print("device " + json.dumps(device), flush=True)

    with Probe() as probe:
        phase_rounds(probe, *ROUND_SHAPE)
        phase_serve(probe, chip_share_config())
    for name, program in KERNEL_PROGRAMS.items():
        counts = probe.records[name]["tpu_custom_call"].get(program, [])
        check(bool(counts) and min(counts) > 0,
              f"{name}: no Pallas kernel in {program}")
    rec = probe.records["B.exact_logits"]       # the peak never decreases
    peak, limit = rec["peak_bytes_in_use"], rec["bytes_limit"]
    headroom = limit - peak
    print("memory " + json.dumps({"peak_bytes_in_use": peak,
                                  "bytes_limit": limit,
                                  "headroom_bytes": headroom}), flush=True)
    check(headroom >= 2 * GIB, f"only {headroom} bytes of headroom")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
