"""Fault injection + defended rounds: determinism, screening, retries,
degradation, health, and the transport-robustness satellites.

The exclusion tests mirror BENCH_faults' acceptance shape: a corrupted
responder must be provably excluded (its decode-mask bit cleared), not
averaged into the output — on plain AND ``encrypt="real"`` rounds.
"""

import json
import threading
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.api import (ClusterSpec, CodeSpec, CryptoSpec, FaultSpec,
                       PrivacySpec, Session, StragglerSpec, TransportSpec,
                       WaitSpec)
from repro.runtime import (DegradedRoundError, FaultInjectingTransport,
                           ResultDropped, ThreadTransport, WorkerHealth,
                           plan_faults, screen_responders)
from repro.runtime.straggler import StragglerModel

SET = dict(max_examples=20, deadline=None)


def _mats(seed=42, m=48, d=32, n_out=16):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((m, d)).astype(np.float32)
    b = rng.standard_normal((d, n_out)).astype(np.float32)
    return a, b


def _spec(**over):
    kw = dict(
        code=CodeSpec(scheme="spacdc", n_workers=24, k_blocks=4,
                      extra={"fh_degree": 3}),
        privacy=PrivacySpec(t_colluding=2, noise_scale=0.01),
        straggler=StragglerSpec(n_stragglers=3), seed=11)
    kw.update(over)
    return ClusterSpec(**kw)


# ---------------------------------------------------------------- FaultSpec

def test_fault_spec_json_roundtrip():
    fs = FaultSpec(crash_rate=0.1, corrupt_rate=0.05, corrupt_mode="bitflip",
                   handle=True, max_retries=3, seed=99)
    back = FaultSpec.from_dict(json.loads(json.dumps(fs.to_dict())))
    assert back == fs
    spec = _spec(fault=fs)
    spec2 = ClusterSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert spec2.fault == fs


@pytest.mark.parametrize("bad", [
    dict(crash_rate=1.5),
    dict(drop_rate=-0.1),
    dict(corrupt_mode="garbage"),
    dict(corrupt_scale=0.0),
    dict(max_retries=-1),
    dict(backoff_s=0.1, backoff_cap_s=0.01),
    dict(worker_timeout_s=0.0),
    dict(residual_threshold=0.0),
    dict(norm_factor=1.0),
    dict(quarantine_after=0),
])
def test_fault_spec_rejects(bad):
    with pytest.raises(ValueError, match="fault:"):
        FaultSpec(**bad)


def test_cluster_validate_rejects_bad_fault_combos():
    fault = FaultSpec(handle=True)
    with pytest.raises(ValueError, match="pair-coded"):
        _spec(code=CodeSpec(scheme="polynomial", n_workers=8, k_blocks=4),
              privacy=PrivacySpec(), fault=fault).validate()
    with pytest.raises(ValueError, match="error_target"):
        _spec(wait=WaitSpec(policy="error_target", eps=1e-2),
              fault=fault).validate()
    with pytest.raises(ValueError, match="crypto.fused"):
        _spec(crypto=CryptoSpec(encrypt="real", fused=True),
              fault=fault).validate()


# ------------------------------------------------------------- determinism

@settings(**SET)
@given(seed=st.integers(0, 2**16), round_idx=st.integers(0, 500))
def test_plan_faults_deterministic(seed, round_idx):
    fault = FaultSpec(crash_rate=0.2, drop_rate=0.1, corrupt_rate=0.2,
                      delay_spike_rate=0.1)
    p1 = plan_faults(fault, seed, round_idx, 16)
    p2 = plan_faults(fault, seed, round_idx, 16)
    for f in ("crash", "drop", "corrupt", "spike_s"):
        np.testing.assert_array_equal(getattr(p1, f), getattr(p2, f))
    # crash/drop/corrupt are mutually exclusive per worker
    both = (p1.crash & p1.drop) | (p1.crash & p1.corrupt) | \
        (p1.drop & p1.corrupt)
    assert not both.any()


def test_plan_faults_varies_with_round():
    fault = FaultSpec(crash_rate=0.3, corrupt_rate=0.3)
    plans = [plan_faults(fault, 7, r, 32) for r in range(20)]
    crash_sets = {tuple(np.flatnonzero(p.crash)) for p in plans}
    assert len(crash_sets) > 1, "every round drew the identical fault plan"


def test_injection_identical_across_backends():
    """The fault plan (and thus which workers crash/corrupt) is a pure
    function of (seed, round) — the wrapped backend doesn't matter."""
    fault = FaultSpec(crash_rate=0.25, corrupt_rate=0.25, seed=3)
    n = 12
    strag = StragglerModel(n_workers=n, n_stragglers=0, seed=0,
                           delay_s=0.0)
    from repro.runtime.transport import VirtualClockTransport
    virt = FaultInjectingTransport(VirtualClockTransport(strag), fault, 3)
    thr_inner = ThreadTransport(n, StragglerModel(
        n_workers=n, n_stragglers=0, seed=0, delay_s=0.0))
    thr = FaultInjectingTransport(thr_inner, fault, 3)
    try:
        arrived = {}
        for name, tr in (("virtual", virt), ("threads", thr)):
            h = tr.submit_round([np.float32(i) for i in range(n)],
                                lambda x: x * 2, 5, t_compute=1e-4)
            evs = list(h.events())
            h.finish()
            arrived[name] = sorted(e.worker for e in evs)
        assert arrived["virtual"] == arrived["threads"]
        plan = plan_faults(fault, 3, 5, n)
        expect = sorted(set(range(n)) - set(np.flatnonzero(plan.crash)))
        assert arrived["virtual"] == expect
    finally:
        thr_inner.close()


# ----------------------------------------------------------- injector paths

def test_injector_drop_and_corrupt_virtual():
    fault = FaultSpec(drop_rate=0.5, corrupt_rate=0.3, corrupt_scale=1e3,
                      seed=0)
    n = 16
    strag = StragglerModel(n_workers=n, n_stragglers=0, seed=0, delay_s=0.0)
    from repro.runtime.transport import VirtualClockTransport
    tr = FaultInjectingTransport(VirtualClockTransport(strag), fault, 0)
    shards = [np.full((4,), float(i), np.float32) for i in range(n)]
    h = tr.submit_round(shards, lambda x: x + 1.0, 0, t_compute=1e-4)
    plan = plan_faults(fault, 0, 0, n)
    assert plan.drop.any() and plan.corrupt.any()
    for ev in h.events():
        w = ev.worker
        if plan.drop[w]:
            with pytest.raises(ResultDropped):
                h.result(w)
        elif plan.corrupt[w]:
            got = h.result(w)
            assert not np.allclose(got, shards[w] + 1.0)
        else:
            np.testing.assert_array_equal(h.result(w), shards[w] + 1.0)
    h.finish()


# --------------------------------------------- screening / mask-bit proofs

def _proof_spec(encrypt=None, cipher_mode="stream"):
    """Corrupt-only, no stragglers, no retries: every worker responds and
    every corrupted responder must end with its slot bit cleared."""
    return _spec(
        straggler=StragglerSpec(n_stragglers=0),
        crypto=CryptoSpec(encrypt=encrypt, cipher_mode=cipher_mode),
        fault=FaultSpec(corrupt_rate=0.25, corrupt_scale=1e3, handle=True,
                        max_retries=0, seed=5))


@pytest.mark.parametrize("encrypt,cipher_mode", [
    (None, "stream"), ("real", "stream"), ("real", "paper")])
def test_corrupted_responder_mask_bit_cleared(encrypt, cipher_mode):
    a, b = _mats()
    ref = a @ b
    spec = _proof_spec(encrypt, cipher_mode)
    plan = plan_faults(spec.fault, spec.fault.seed, 0, spec.code.n_workers)
    corrupted = set(int(w) for w in np.flatnonzero(plan.corrupt))
    assert corrupted, "seed must inject at least one corrupter in round 0"
    with Session(spec) as s:
        out, stats = s.matmul(a, b)
    # provably excluded: the exact corrupted set, nothing else; with
    # max_retries=0 and the identity assignment, worker w held slot w,
    # so its decode-mask bit must be cleared
    assert set(stats.excluded) == corrupted
    for w in corrupted:
        assert stats.decode_mask[w] == 0
    rel = np.linalg.norm(out - ref) / np.linalg.norm(ref)
    assert rel < 1e-2, f"corruption leaked into the decode: rel={rel:.3e}"


def test_clean_output_bit_identical_plain_vs_real():
    """The bits codec is lossless: a clean defended round decodes to the
    SAME float32 output whether shards travelled in the clear or as
    genuine ciphertexts — in both cipher modes."""
    a, b = _mats()
    outs = []
    for encrypt, mode in ((None, "stream"), ("real", "stream"),
                          ("real", "paper")):
        spec = _spec(crypto=CryptoSpec(encrypt=encrypt, cipher_mode=mode),
                     fault=FaultSpec(handle=True))
        with Session(spec) as s:
            out, stats = s.matmul(a, b)
        assert stats.excluded == () and stats.retries == 0
        outs.append(np.asarray(out))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_tampered_ciphertext_caught_like_plain_corruption():
    """Ciphertext tampering on the wire and plaintext corruption at the
    same (seed, round) evict the same workers — screening sees through
    the cipher layer (tampered limbs decrypt to garbage that the
    norm/residual stages reject identically)."""
    a, b = _mats()
    excl = {}
    for encrypt in (None, "real"):
        spec = _proof_spec(encrypt)
        with Session(spec) as s:
            out, stats = s.matmul(a, b)
        excl[encrypt] = set(stats.excluded)
    assert excl[None] == excl["real"] and excl[None]


def test_screen_responders_norm_stage_handles_many_corrupters():
    """The regime LOO alone can't separate: several corrupters pollute
    every leave-one-out prediction, but the median row norm stays at
    signal scale."""
    from repro.core import registry
    sch = registry.build("spacdc", n_workers=20, k_blocks=4, t_colluding=2,
                         noise_scale=0.01, seed=1)
    rng = np.random.default_rng(0)
    a, b = _mats(seed=1)
    enc = np.asarray(sch.encode(a))
    results = np.einsum("nij,jk->nik", enc, b)
    bad = [2, 7, 11, 15]
    for w in bad:
        results[w] = results[w] * 1e3 + rng.standard_normal(
            results[w].shape).astype(np.float32) * 1e3
    mask = np.ones(20, np.float32)
    clean_mask, excluded, _ = screen_responders(
        sch, results, mask, max_exclude=10)
    assert set(excluded) == set(bad)
    assert all(clean_mask[w] == 0.0 for w in bad)


def test_screen_responders_clean_round_no_false_positives():
    from repro.core import registry
    sch = registry.build("spacdc", n_workers=24, k_blocks=6, t_colluding=2,
                         noise_scale=0.05, seed=7)
    a, b = _mats()
    enc = np.asarray(sch.encode(a))
    results = np.einsum("nij,jk->nik", enc, b)
    mask = np.ones(24, np.float32)
    _, excluded, _ = screen_responders(sch, results, mask, max_exclude=20)
    assert excluded == []


# -------------------------------------------------- retries / degradation

def test_defended_round_retries_and_records_stats():
    a, b = _mats()
    ref = a @ b
    spec = _spec(fault=FaultSpec(crash_rate=0.12, corrupt_rate=0.12,
                                 corrupt_scale=1e3, handle=True,
                                 quarantine_after=2))
    total_retries = total_excluded = 0
    with Session(spec) as s:
        for _ in range(6):
            out, st = s.matmul(a, b)
            rel = np.linalg.norm(out - ref) / np.linalg.norm(ref)
            assert rel < 1e-2
            assert len(st.decode_mask) == spec.code.n_workers
            assert sum(st.decode_mask) == st.n_waited
            total_retries += st.retries
            total_excluded += len(st.excluded)
        assert s.health is not None
        snap = s.health.snapshot()
    assert total_retries >= 1
    assert total_excluded >= 1
    assert sum(snap["n_corrupt"]) >= 1


def test_rateless_degraded_round_reports_achieved_err():
    a, b = _mats()
    spec = _spec(
        straggler=StragglerSpec(n_stragglers=0),
        fault=FaultSpec(crash_rate=0.5, handle=True, max_retries=0,
                        seed=13))
    with Session(spec) as s:
        out, st = s.matmul(a, b)
    assert st.degraded
    assert st.achieved_rel_err is not None and st.achieved_rel_err >= 0
    assert out.shape == (a.shape[0], b.shape[1])


def test_threshold_scheme_raises_structured_degraded_error():
    a, b = _mats(m=32, d=16, n_out=8)
    spec = ClusterSpec(
        code=CodeSpec(scheme="mds", n_workers=8, k_blocks=4),
        straggler=StragglerSpec(n_stragglers=0), seed=2,
        fault=FaultSpec(crash_rate=0.9, handle=True, max_retries=1,
                        seed=21))
    with Session(spec) as s:
        with pytest.raises(DegradedRoundError) as ei:
            for _ in range(6):   # some round draws > n-k crashes
                s.matmul(a, b)
    err = ei.value
    assert err.needed >= 4
    assert len(err.clean_slots) < 4
    assert err.retries >= 0 and isinstance(err.excluded, tuple)


# ------------------------------------------------------------ WorkerHealth

def test_worker_health_quarantine_and_probation():
    h = WorkerHealth(4, quarantine_after=2, quarantine_rounds=3,
                     probation_ok=2)
    h.record_corrupt(1, 0)
    assert not h.is_quarantined(1, 1)
    h.record_corrupt(1, 1)          # second strike -> quarantined
    assert h.is_quarantined(1, 2)
    assert not h.is_quarantined(1, 5)   # 3 rounds served
    # offense during probation -> re-quarantined, doubled
    h.record_crash(1, 5)
    assert h.is_quarantined(1, 6)
    assert h.is_quarantined(1, 5 + 5)   # 2x quarantine_rounds
    # a clean streak through probation clears the slate
    h.record_ok(2, 0.01)
    assert 2 in h.ranked(1)
    assert 1 not in h.ranked(6)
    assert 1 not in h.ranked(6, exclude={1})


def test_worker_health_ranked_prefers_fast_workers():
    h = WorkerHealth(3)
    h.record_ok(0, 0.5)
    h.record_ok(1, 0.01)
    h.record_ok(2, 0.1)
    assert h.ranked(1) == [1, 2, 0]


# ------------------------------------------- transport satellites (a + b)

def _failing_straggler():
    """Worker 1 starts, then fails only once ``release`` is set: the round
    can be finished while it is known to be running, however loaded the
    host (a straggler still queued at ``finish()`` is cancelled, not
    failed)."""
    started, release = threading.Event(), threading.Event()

    def f(x):
        if x == 1:
            started.set()
            release.wait(5.0)
            raise RuntimeError("boom")
        return x

    return f, started, release


def test_stray_failure_tagged_with_originating_round():
    tr = ThreadTransport(2, StragglerModel(n_workers=2, n_stragglers=0,
                                           seed=0, delay_s=0.0))
    try:
        f, started, release = _failing_straggler()
        h = tr.submit_round([0, 1], f, round_idx=5, t_compute=1e-4)
        it = h.events()
        ev = next(it)           # consume the healthy worker only
        assert ev.worker == 0
        assert started.wait(5.0)
        h.finish()              # straggler still running: no error yet
        release.set()
        time.sleep(0.4)         # let the failure land
        with pytest.raises(RuntimeError,
                           match=r"originating round 5") as ei:
            h.finish()
        assert "boom" in str(ei.value.__cause__)
    finally:
        tr.close()


def test_stray_failure_still_surfaces_on_next_submit():
    tr = ThreadTransport(2, StragglerModel(n_workers=2, n_stragglers=0,
                                           seed=0, delay_s=0.0))
    try:
        f, started, release = _failing_straggler()
        h = tr.submit_round([0, 1], f, round_idx=3, t_compute=1e-4)
        next(h.events())
        assert started.wait(5.0)
        h.finish()
        release.set()
        time.sleep(0.4)
        with pytest.raises(RuntimeError, match=r"originating round 3"):
            tr.submit_round([0, 1], lambda x: x, 4, t_compute=1e-4)
    finally:
        tr.close()


def test_close_does_not_deadlock_on_blocked_worker():
    """Regression (satellite): Session/transport close used to join the
    executor unbounded — a crashed/never-arriving worker thread would
    hang shutdown forever."""
    tr = ThreadTransport(2, StragglerModel(n_workers=2, n_stragglers=0,
                                           seed=0, delay_s=0.0))
    tr.join_timeout_s = 0.3
    release = threading.Event()

    def f(x):
        if x == 1:
            release.wait()      # blocked until the test releases it
        return x

    h = tr.submit_round([0, 1], f, round_idx=0, t_compute=1e-4)
    next(h.events())
    h.finish()
    t0 = time.perf_counter()
    tr.close()
    elapsed = time.perf_counter() - t0
    release.set()               # let the abandoned thread exit cleanly
    assert elapsed < 1.5, f"close() blocked {elapsed:.2f}s on a stuck worker"


def test_session_close_bounded_with_inflight_threads_round():
    a, b = _mats(m=16, d=8, n_out=4)
    spec = _spec(
        code=CodeSpec(scheme="spacdc", n_workers=6, k_blocks=2,
                      fused=False, extra={"fh_degree": 3}),
        straggler=StragglerSpec(n_stragglers=2, delay_s=0.05),
        transport=TransportSpec(backend="threads"))
    s = Session(spec)
    s.matmul(a, b)              # leaves stragglers sleeping on the pool
    t0 = time.perf_counter()
    s.close()
    assert time.perf_counter() - t0 < 5.0
