"""Driver for coded matmul jobs: back-to-back rounds of ``Session.matmul``
on one pair of operands, each round a fresh straggler draw.

Set-up makes A and B on the device from the seed in one jitted call, opens
the session and runs two rounds (the first compiles).  The window runs
rounds until ``--seconds`` have passed; a round ends when ``Session.matmul``
has returned its product on the host.  ``round_ms`` is the window's wall
over the rounds completed in it.  A sample of the window's rounds, drawn
from the seed, is kept with the responder set each decoded from, and is
compared with the configuration's reference once the window has closed and
the session is freed.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from yardstick import harness, peaks, work
from yardstick.harness import Check, Outcome, span


class Rounds:
    """One session and its operands: the timed path of a round cell."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        from repro.api import Session
        self.spec = harness.cluster_spec(config, traffic, seed)
        shape = config["shape"]
        m, d, n = shape["a_rows"], shape["d"], shape["b_cols"]

        @jax.jit
        def operands(key):
            ka, kb = jax.random.split(key)
            return (jax.random.normal(ka, (m, d), "float32"),
                    jax.random.normal(kb, (d, n), "float32"))

        self.a, self.b = jax.block_until_ready(operands(harness.seed_key(seed)))
        self.session = Session(self.spec)
        self.rounds = 0

    def round(self):
        """One round: (host product, sorted responder indices)."""
        with span("round"):
            out, stats = self.session.matmul(self.a, self.b)
        self.rounds += 1
        resp = sorted(int(w) for _, w in stats.arrivals[:stats.n_waited])
        return out, resp

    def window(self, seconds: float, keep: int, rng):
        """Rounds until ``seconds`` have passed.  Returns (per-round walls,
        window wall, sample): the sample is a uniform reservoir of ``keep``
        (round, product, responders), drawn with ``rng``."""
        walls, sample = [], []
        t_start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            out, resp = self.round()
            t1 = time.perf_counter()
            walls.append(t1 - t0)
            i = len(walls) - 1
            if i < keep:
                sample.append((i, out, resp))
            else:
                j = int(rng.integers(0, i + 1))
                if j < keep:
                    sample[j] = (i, out, resp)
            if t1 - t_start >= seconds:
                return walls, t1 - t_start, sample

    def close(self):
        self.session.close()


def readings(config: dict, rounds: Rounds, sample, *, control: bool = False):
    """The compared number, ``round_rel_gap``: the widest gap over the
    sample between the program's product (or the control's) and the
    float64 reference, relative to the reference's largest entry."""
    ref = harness.load_module(harness.bench_dir() / "configs"
                              / f"{config['name']}.py")
    code = dict(config["cluster"]["code"], **config["cluster"]["privacy"])
    seed = rounds.spec.seed
    resp = [r for _, _, r in sample]
    refs = ref.reference(rounds.a, rounds.b, resp, code, seed)
    if control:
        outs = ref.control(rounds.a, rounds.b, resp, code, seed)
    else:
        outs = (o for _, o, _ in sample)
    return ref.widest_gap(outs, refs)


def run(config: dict, traffic: dict, ctx) -> Outcome:
    import jax
    rounds = Rounds(config, traffic, ctx.seed)
    ctx.mark("operands_and_session")
    for _ in range(2):                      # compile, then one steady round
        rounds.round()
    ctx.mark("warm_rounds")
    gc.collect()
    rng = np.random.default_rng(ctx.seed)
    setup_s = ctx.since_start()
    traces = rounds.session.engine.trace_count
    with harness.CompileWatch() as watch:
        walls, wall, sample = rounds.window(ctx.seconds,
                                            traffic["check_rounds"], rng)
    compiles = watch.count + rounds.session.engine.trace_count - traces
    memory = harness.memory_peak_bytes()

    measure = breakdown = None
    if ctx.trace:
        traced = {}
        n0 = rounds.rounds
        with harness.traced(ctx, traced):
            t0 = time.perf_counter()
            while (rounds.rounds - n0 < traffic["trace_min_rounds"]
                   or time.perf_counter() - t0 < traffic["trace_seconds"]):
                rounds.round()
        traced["summary"].check_complete("jit__round", rounds.rounds - n0)
        shape = config["shape"]
        m, d, n = shape["a_rows"], shape["d"], shape["b_cols"]
        code = rounds.spec.code
        measure = {
            "kind": "round", "summary": traced["summary"],
            "units": rounds.rounds - n0, "unit_walls_s": walls,
            "peaks": peaks.peaks_for(jax.devices()[0].device_kind),
            "uncoded_flops": work.matmul_flops(m, d, n),
            "kernel_work": {"coded_matmul_kernel": work.coded_round_work(
                m, d, n, code.n_workers, code.k_blocks,
                rounds.spec.privacy.t_colluding)},
        }
        breakdown = traced["summary"].breakdown()

    rounds.close()
    gc.collect()
    gap = readings(config, rounds, sample)
    checks = [Check("round_rel_gap", gap, config["limits"]["round_rel_gap"]),
              Check("compiles_in_window", compiles, 0)]
    return Outcome(attempted=len(walls), failed=0,
                   end_to_end={"round_ms": 1e3 * wall / len(walls),
                               "setup_s": setup_s},
                   checks=checks, memory_peak_bytes=memory,
                   measure=measure, breakdown=breakdown)


def control_readings(config: dict, traffic: dict, seed: int,
                     seconds: float) -> dict:
    """One seed's ``round_rel_gap`` for the program's rounds and for the
    control in their place, after a window of ``seconds``."""
    rounds = Rounds(config, traffic, seed)
    for _ in range(2):
        rounds.round()
    _, _, sample = rounds.window(seconds, traffic["check_rounds"],
                                 np.random.default_rng(seed))
    rounds.close()
    return {"round_rel_gap": (readings(config, rounds, sample),
                              readings(config, rounds, sample, control=True))}
