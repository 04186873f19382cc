#!/usr/bin/env python
"""Policy-serving microbench: QPS + tail latency of the inference tier.

Measures the ``blendjax/serve`` tier end-to-end over loopback TCP — N
concurrent episode clients (threads) against an in-process
:class:`~blendjax.serve.server.PolicyServer` — in two modes kept
alive for the whole run and compared over interleaved, order-rotated
rounds (the drift-immune house scheme):

- **batched**: continuous batching over the ROUTER socket (admission
  queue -> pad-to-bucket -> one jitted call per tick);
- **int8** (``--int8``, default on): the same batched server on the
  ``ops/quant``-quantized model — ``serve_int8_x = int8/batched``.

Headline: ``serve_qps`` (median batched round) and ``serve_p99_ms``
(client-observed per-request latency, merged across every batched
round's per-client histograms — a real union quantile).  A **prefill**
phase prices batched prefill admission (``reset`` with a T-step
observation prefix replayed in one teacher-forced pass) against T
serial steps: ``serve_prefill_x`` = serial/prefill admission time at
the median interleaved pair.  One JSON line; keys locked by
``benchmarks/_common.SERVE_BENCH_KEYS``.

``--gateway --replicas N`` switches to the **fleet** bench
(``make gatewaybench``): N replica *processes* behind one in-process
:class:`~blendjax.serve.gateway.ServeGateway`, measured over
interleaved 1-replica vs N-replica windows — the 1-replica windows
DRAIN all but replica 0 (the gateway's rolling-restart primitive doing
double duty), so both arms run the same sockets, the same gateway hop
and the same fleet, and the ratio isolates replica-level scale-out.
``gateway_scale_x`` is the median per-pair ratio, ``gateway_qps`` /
``gateway_p99_ms`` the N-replica aggregate QPS and client-observed
union p99.  Replicas serve the linear model with a sleep-based per-row
``--work-us`` compute stand-in (the RL bench's ``physics_us`` pattern)
so replica compute — not the loopback wire — is the bottleneck being
scaled; keys locked by ``GATEWAY_BENCH_KEYS``.  See docs/serving.md.

``--scenario-mix`` switches to the **labelled traffic mix** arm
(docs/scenarios.md): the same batched server and the same client loop,
driven by a weighted set of :class:`RequestProfile` shapes (per-label
episode length and step cadence) instead of one synthetic shape —
per-scenario QPS/p99 plus ``serve_mix_p99_ms``, the union tail latency
a realistic multi-scenario workload observes.  All three arms share
the one profile-driven client loop; the legacy arms are simply the
single-profile case.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from blendjax.obs.histogram import LatencyHistogram  # noqa: E402


def _build_models(model, *, obs_dim, d_model, n_heads, n_layers, slots,
                  length, seed, int8):
    """(float_model, int8_model|None) sharing weights."""
    if model == "linear":
        from blendjax.serve.server import LinearModel

        mk = lambda: LinearModel(obs_dim=obs_dim, slots=slots, seed=seed)
        return mk(), (mk() if int8 else None)
    if model == "policy":
        import jax

        from blendjax.models import policy
        from blendjax.serve.server import PolicyModel

        params = policy.init(jax.random.PRNGKey(seed), obs_dim, 8)
        return (
            PolicyModel(params, obs_dim),
            PolicyModel(params, obs_dim, int8=True) if int8 else None,
        )
    if model == "seqformer":
        import jax

        from blendjax.models import seqformer
        from blendjax.serve.server import SeqFormerModel

        # rope: no learned-table horizon, so long bench windows ring
        # through the cache instead of clamping position embeddings
        params = seqformer.init(
            jax.random.PRNGKey(seed), obs_dim=obs_dim, d_model=d_model,
            n_heads=n_heads, n_layers=n_layers, pos_encoding="rope",
        )
        mk = lambda **kw: SeqFormerModel(params, slots, length, **kw)
        return mk(), (mk(int8=True) if int8 else None)
    raise ValueError(f"unknown model {model!r}")


def _warm_buckets(server, clients):
    """Pre-compile every bucket a window can hit (one XLA compilation
    each) so the timed rounds measure serving, not compilation."""
    model = server.model
    for b in server.buckets:
        idx = np.full(b, model.pad_slot, np.int64)
        model.step_rows(idx, np.zeros((b, model.obs_dim), np.float32))
        if b >= max(1, clients):
            break


class RequestProfile:
    """One client workload shape — the single-client-shape assumption
    the legacy arms baked in, factored into an object so the legacy
    arms and the ``--scenario-mix`` arm share ONE client loop.

    Params
    ------
    obs_dim: int
        Observation width each ``step`` sends.
    episode_len: int
        Steps per episode before close+reset (the admission rate).
    scenario: str | None
        Traffic label stamped on every admission (``reset(scenario=)``)
        so a fronting gateway attributes the episode's requests to its
        per-scenario records; None = unlabelled (the legacy arms).
    weight: float
        Share of clients this profile claims in a mix window
        (largest-remainder apportionment over the client count).
    think_us: int
        Client-side pause between steps — a slow-cadence scenario's
        request shape (0 = closed-loop as fast as replies arrive).
    """

    __slots__ = ("obs_dim", "episode_len", "scenario", "weight",
                 "think_us")

    def __init__(self, obs_dim, episode_len, *, scenario=None,
                 weight=1.0, think_us=0):
        self.obs_dim = int(obs_dim)
        self.episode_len = max(1, int(episode_len))
        self.scenario = scenario
        self.weight = float(weight)
        self.think_us = int(think_us)


def assign_profiles(profiles, clients):
    """Per-client profile list from a weighted profile set
    (largest-remainder over the client count, profile order breaking
    ties — deterministic).  A single profile fans out to every
    client."""
    if isinstance(profiles, RequestProfile):
        return [profiles] * clients
    profiles = list(profiles)
    total = sum(max(p.weight, 0.0) for p in profiles) or 1.0
    quotas = [max(p.weight, 0.0) / total * clients for p in profiles]
    counts = [int(q) for q in quotas]
    order = sorted(
        range(len(profiles)),
        key=lambda i: (-(quotas[i] - int(quotas[i])), i),
    )
    for i in order[:clients - sum(counts)]:
        counts[i] += 1
    out = []
    for p, k in zip(profiles, counts):
        out.extend([p] * k)
    return out[:clients]


def _run_window(address, profiles, seconds, clients):
    """One timed window of ``clients`` concurrent episode loops, each
    driving its assigned :class:`RequestProfile`; returns ``(qps,
    merged client-observed latency histogram, per-scenario
    {label: (count, histogram)})`` — the per-scenario dict is empty
    for unlabelled (legacy single-shape) windows."""
    assigned = assign_profiles(profiles, clients)
    hists = [LatencyHistogram() for _ in range(clients)]
    counts = [0] * clients
    # two barriers so the clock starts only once EVERY client is
    # connected and reset-ready: ready collects them, the deadline is
    # stamped between the barriers, go releases — thread spawn and
    # reset latency never eat the measured window, and every client
    # stops at the same wall deadline so ``seconds`` is the honest
    # denominator (teardown close/join excluded)
    ready = threading.Barrier(clients + 1)
    go = threading.Barrier(clients + 1)
    t_deadline = [None]
    errors = []

    def runner(i):
        from blendjax.serve.client import ServeClient

        prof = assigned[i]
        client = ServeClient(address, timeoutms=10000)
        rng = np.random.default_rng(1000 + i)
        obs = rng.standard_normal(prof.obs_dim).astype(np.float32)
        think_s = prof.think_us / 1e6
        try:
            client.reset(scenario=prof.scenario)
            # throwaway steps so transport negotiation (the shm
            # upgrade probe — attach or permanent refusal, which
            # triggers after UPGRADE_AFTER rpcs) settles BEFORE the
            # clock: the window measures steady state, not
            # first-contact channel churn
            client.step(obs)
            client.step(obs)
            ready.wait(timeout=30)
            go.wait(timeout=30)
            end = t_deadline[0]
            n = steps = 0
            while time.perf_counter() < end:
                t0 = time.perf_counter()
                client.step(obs)
                hists[i].add(time.perf_counter() - t0)
                n += 1
                steps += 1
                if steps >= prof.episode_len:
                    client.close_episode()
                    client.reset(scenario=prof.scenario)
                    steps = 0
                if think_s:
                    time.sleep(think_s)
            counts[i] = n
        except Exception as exc:  # noqa: BLE001 - must not corrupt qps
            # a dead client thread would silently deflate the window's
            # counts and histogram — surface it as a failed window (and
            # break the barriers so a pre-start death fails fast)
            errors.append(f"client {i}: {type(exc).__name__}: {exc}")
            ready.abort()
            go.abort()
        finally:
            try:
                client.close_episode()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
            client.close()

    threads = [threading.Thread(target=runner, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    broken = False
    try:
        ready.wait(timeout=60)
        t_deadline[0] = time.perf_counter() + seconds
        go.wait(timeout=30)
    except threading.BrokenBarrierError:
        broken = True  # a client died pre-start; reported below
    for t in threads:
        t.join(timeout=seconds + 30)
    if errors or broken:
        raise RuntimeError(
            f"serve bench window lost {len(errors)} client(s): "
            + ("; ".join(errors) or "barrier broken")
        )
    merged = LatencyHistogram()
    for h in hists:
        merged.merge(h)
    by_scenario = {}
    for i, prof in enumerate(assigned):
        if prof.scenario is None:
            continue
        cnt, h = by_scenario.setdefault(
            prof.scenario, [0, LatencyHistogram()]
        )
        by_scenario[prof.scenario][0] = cnt + counts[i]
        h.merge(hists[i])
    return sum(counts) / seconds, merged, by_scenario


def _measure_prefill(address, obs_dim, *, prefix_len=32, admissions=4,
                     pairs=2, seed=7):
    """Batched prefill admission vs T serial steps: time ``admissions``
    episode admissions with a ``prefix_len``-step observation prefix
    through ``reset(prefix=...)`` (one teacher-forced pass) and through
    ``reset()`` + T ``step()``s, in interleaved order-alternating
    pairs.  Returns the prefill sub-record; ``serve_prefill_x`` is the
    median per-pair serial/prefill time ratio (>1 = prefill wins)."""
    from blendjax.serve.client import ServeClient

    client = ServeClient(address, timeoutms=30000)
    prefix = np.random.default_rng(seed).standard_normal(
        (prefix_len, obs_dim)
    ).astype(np.float32)

    def admit_prefill():
        client.reset(prefix=prefix)
        client.close_episode()

    def admit_serial():
        client.reset()
        for t in range(prefix_len):
            client.step(prefix[t])
        client.close_episode()

    try:
        # warm both arms (prefill compiles once per prefix length)
        admit_prefill()
        admit_serial()
        t_pre, t_ser = [], []
        for p in range(pairs):
            arms = [admit_prefill, admit_serial]
            sinks = [t_pre, t_ser]
            if p % 2:
                arms.reverse()
                sinks.reverse()
            for arm, sink in zip(arms, sinks):
                t0 = time.perf_counter()
                for _ in range(admissions):
                    arm()
                sink.append(time.perf_counter() - t0)
    finally:
        client.close()
    ratios = [round(s / p, 3) for p, s in zip(t_pre, t_ser) if p > 0]
    return {
        "prefix_len": prefix_len,
        "admissions": admissions,
        "pairs": pairs,
        "prefill_admits_per_sec": round(
            admissions / float(np.median(t_pre)), 2
        ),
        "serial_admits_per_sec": round(
            admissions / float(np.median(t_ser)), 2
        ),
        "pair_ratios": ratios,
        "serve_prefill_x": (
            round(float(np.median(ratios)), 3) if ratios else None
        ),
    }


def measure(seconds=12.0, clients=8, model="seqformer", *, obs_dim=8,
            d_model=64, n_heads=4, n_layers=2, slots=None, length=64,
            episode_len=32, rounds=None, int8=True, seed=0,
            tick_ms=1.0):
    """Run the batched/int8 comparison; returns the serve_bench record."""
    from blendjax.serve.server import start_server_thread
    from blendjax.utils.timing import EventCounters, StageTimer

    slots = slots or max(2 * clients, 16)
    f_model, q_model = _build_models(
        model, obs_dim=obs_dim, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, slots=slots, length=length, seed=seed,
        int8=int8,
    )
    rounds = rounds or 3
    window_s = max(0.5, seconds / (rounds * (2 if int8 else 1)))
    timer = StageTimer()
    servers = {
        "batched": start_server_thread(
            f_model, counters=EventCounters(), timer=timer,
            tick_ms=tick_ms,
        ),
    }
    if int8:
        servers["int8"] = start_server_thread(
            q_model, counters=EventCounters(), timer=StageTimer(),
            tick_ms=tick_ms,
        )
    profile = RequestProfile(obs_dim, episode_len)
    qps = {name: [] for name in servers}
    batched_hist = LatencyHistogram()
    try:
        for name, h in servers.items():
            _warm_buckets(h.server, clients)
            _run_window(h.address, profile, 0.3, clients)
        order = list(servers)
        for r in range(rounds):
            rotated = order[r % len(order):] + order[:r % len(order)]
            for name in rotated:
                rate, hist, _ = _run_window(
                    servers[name].address, profile, window_s, clients,
                )
                qps[name].append(rate)
                if name == "batched":
                    batched_hist.merge(hist)
        # prefill admission vs serial replay, on the live batched
        # server (stateful models only — it needs a KV cache to fill)
        prefill = (
            _measure_prefill(
                servers["batched"].address, obs_dim,
                prefix_len=min(32, max(4, length // 2)),
            )
            if f_model.slots > 0 else None
        )
    finally:
        for h in servers.values():
            h.close()
    med = {name: float(np.median(rates)) for name, rates in qps.items()}
    pct = batched_hist.percentiles()
    out = {
        "model": model,
        "clients": clients,
        "slots": slots,
        "obs_dim": obs_dim,
        "rounds": rounds,
        "window_s": round(window_s, 3),
        "episode_len": episode_len,
        "serve_qps": round(med["batched"], 2),
        "serve_p50_ms": pct["p50_ms"],
        "serve_p99_ms": pct["p99_ms"],
        "serve_int8_x": (
            round(med["int8"] / med["batched"], 3)
            if int8 and med.get("batched") else None
        ),
        "serve_prefill_x": (
            prefill["serve_prefill_x"] if prefill else None
        ),
        "prefill": prefill,
        "serve_qps_modes": {k: round(v, 2) for k, v in med.items()},
        "stages": {
            k: v for k, v in timer.summary().items()
            if k in ("queue_wait", "batch_assemble", "compute", "reply")
        },
    }
    return out


def _client_proc_main(address, profiles, seconds, clients, ready, go,
                      outq):
    """Entry point of one ``--client-procs`` worker: runs a share of
    the window's clients (threads) in its OWN process, so client-side
    request encode/decode never contends with the front/gateway thread
    for the parent's GIL.  Imports happen before the ready barrier, so
    the measured windows align across processes."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        from blendjax.serve import client as _  # noqa: F401 - preimport

        ready.wait(timeout=120)
        go.wait(timeout=120)
        qps, hist, _scen = _run_window(address, profiles, seconds,
                                       clients)
        outq.put(("ok", qps, hist.to_dict()))
    except Exception as exc:  # noqa: BLE001 - surfaced in the parent
        outq.put(("err", f"{type(exc).__name__}: {exc}", None))


def _run_window_procs(address, profiles, seconds, clients, procs):
    """``_run_window`` with the client threads spread over ``procs``
    worker PROCESSES (spawn — never fork a process that holds live
    server threads).  Same return shape; per-scenario breakdown is not
    carried across the process boundary (the mix arm stays
    in-process)."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    shares = [clients // procs + (1 if i < clients % procs else 0)
              for i in range(procs)]
    shares = [s for s in shares if s]
    ready = ctx.Barrier(len(shares) + 1)
    go = ctx.Barrier(len(shares) + 1)
    outq = ctx.Queue()
    workers = [
        ctx.Process(
            target=_client_proc_main,
            args=(address, profiles, seconds, share, ready, go, outq),
            daemon=True,
        )
        for share in shares
    ]
    for w in workers:
        w.start()
    try:
        ready.wait(timeout=180)
        go.wait(timeout=60)
        results = [outq.get(timeout=seconds + 180) for _ in workers]
    finally:
        for w in workers:
            w.join(timeout=30)
            if w.is_alive():
                w.terminate()
    errors = [r[1] for r in results if r[0] == "err"]
    if errors:
        raise RuntimeError(
            f"bench client process(es) failed: {'; '.join(errors)}"
        )
    merged = LatencyHistogram()
    for r in results:
        merged.merge(LatencyHistogram.from_dict(r[2]))
    return sum(r[1] for r in results), merged, {}


def measure_gateway(seconds=18.0, clients=16, replicas=3, *, obs_dim=8,
                    work_us=2000, episode_len=32, rounds=3, slots=None,
                    seed=0, tick_ms=1.0, scrape_interval_s=0.2,
                    gateway_workers=1, client_procs=0,
                    shard_work_us=500, shard_obs_dim=128,
                    shard_clients=None):
    """The fleet bench: N linear-model replica processes behind one
    gateway, interleaved 1-replica (others DRAINED) vs N-replica
    windows (``gateway_scale_x``).

    ``gateway_workers > 1`` runs the SHARDED gateway (front + worker
    processes + control plane, docs/serving.md) and ADDS a second
    phase over its own fleet (``shard_work_us``/``shard_obs_dim`` —
    a gateway-bound shape: light replica work, fat observations, so
    the data-plane hop is what the window measures, not replica
    sleep-compute): interleaved same-fleet pairs of the data plane
    collapsed to the UNSHARDED single-address shape
    (``set_active_workers(1)`` — same worker processes, same front,
    but no direct-dial map: every message relays through the front's
    one event loop onto one worker, which is what a monolithic
    gateway deployment looks like to clients) vs full partitioned
    direct dial.  ``gateway_shard_x`` is the N-worker/1-worker QPS
    ratio at the median same-round pair, the data-plane sharding win
    in isolation; the scale pair stays on the original replica-bound
    fleet so ``gateway_qps``/``gateway_scale_x``/``gateway_p99_ms``
    remain comparable with pre-shard artifacts.  ``client_procs > 0``
    moves the window's client threads into that many processes (GIL
    isolation on small CI boxes — the record carries the value so
    before/after artifacts are comparable).  Returns the
    gateway_bench record."""
    from blendjax.serve.gateway import (
        start_gateway_thread,
        start_sharded_gateway_thread,
    )
    from blendjax.serve.server import ServerFleet
    from blendjax.utils.timing import EventCounters, StageTimer

    replicas = int(replicas)
    gateway_workers = max(1, int(gateway_workers))
    client_procs = max(0, int(client_procs))
    sharded = gateway_workers > 1
    slots = slots or max(2 * clients, 16)
    # the shard phase adds rounds*2 windows of its own, carved from the
    # same wall budget so --seconds stays the honest total
    windows_per_round = 4 if sharded else 2
    window_s = max(0.5, seconds / (rounds * windows_per_round))
    counters, timer = EventCounters(), StageTimer()
    profile = RequestProfile(obs_dim, episode_len)

    def mk_run(prof):
        if client_procs:
            return lambda addr, s: _run_window_procs(
                addr, prof, s, clients, client_procs)
        return lambda addr, s: _run_window(addr, prof, s, clients)

    run = mk_run(profile)
    qps_one, qps_all = [], []
    all_hist = LatencyHistogram()
    with ServerFleet(replicas, model="linear", obs_dim=obs_dim,
                     slots=slots, seed=seed, tick_ms=tick_ms,
                     work_us=work_us) as fleet:
        if sharded:
            gw = start_sharded_gateway_thread(
                fleet.addresses, workers=gateway_workers,
                counters=counters, timer=timer,
                scrape_interval_s=scrape_interval_s,
            )
        else:
            gw = start_gateway_thread(
                fleet.addresses, counters=counters, timer=timer,
                scrape_interval_s=scrape_interval_s,
            )
        rest = [f"r{i}" for i in range(1, replicas)]

        def run_one():
            # drain everything but r0: same gateway, same sockets,
            # same fleet — only the replica count differs.  Sharded:
            # the drain flag reaches workers via the next control
            # snapshot, so wait out a publish interval
            for rid in rest:
                gw.gateway.drain(rid)
            time.sleep(3 * scrape_interval_s if sharded else 0.05)
            try:
                rate, _, _ = run(gw.address, window_s)
            finally:
                for rid in rest:
                    gw.gateway.undrain(rid)
                if sharded:
                    time.sleep(3 * scrape_interval_s)
            return rate

        def run_all():
            rate, hist, _ = run(gw.address, window_s)
            all_hist.merge(hist)
            return rate

        arms = [("one", run_one, qps_one), ("all", run_all, qps_all)]
        try:
            _run_window(gw.address, profile, 0.3, clients)
            for r in range(rounds):
                rot = arms[r % len(arms):] + arms[:r % len(arms)]
                for _name, fn, sink in rot:
                    sink.append(fn())
        finally:
            gw.close()
    # -- shard phase: 1-worker (single-address relay) vs N-worker
    # (partitioned direct dial) over its OWN gateway-bound fleet —
    # light replica work + fat observations so the window measures the
    # data-plane hop, not replica sleep-compute (the scale pair above
    # keeps the replica-bound fleet for artifact comparability)
    qps_one_worker, qps_nworker = [], []
    shard_counters = {}
    if sharded:
        # default caps the shard phase at 12 clients: on a small box
        # more client threads saturate the core and flatten both arms
        # to the same CPU ceiling, hiding the relay penalty
        sclients = int(shard_clients or min(clients, 12))
        sprofile = RequestProfile(shard_obs_dim, episode_len)
        if client_procs:
            srun = lambda addr, s: _run_window_procs(  # noqa: E731
                addr, sprofile, s, sclients, client_procs)
        else:
            srun = lambda addr, s: _run_window(  # noqa: E731
                addr, sprofile, s, sclients)
        sslots = max(2 * sclients, 16)
        with ServerFleet(replicas, model="linear",
                         obs_dim=shard_obs_dim, slots=sslots,
                         seed=seed, tick_ms=min(tick_ms, 0.5),
                         work_us=shard_work_us) as sf:
            sgw = start_sharded_gateway_thread(
                sf.addresses, workers=gateway_workers,
                counters=counters, timer=timer,
                scrape_interval_s=scrape_interval_s,
            )

            def run_one_worker():
                sgw.set_active_workers(1)
                try:
                    rate, _, _ = srun(sgw.address, window_s)
                finally:
                    sgw.set_active_workers(gateway_workers)
                return rate

            def run_nworker():
                rate, _, _ = srun(sgw.address, window_s)
                return rate

            sarms = [("one_worker", run_one_worker, qps_one_worker),
                     ("nworker", run_nworker, qps_nworker)]
            try:
                # warm BOTH plane shapes so neither timed arm pays
                # first-contact channel negotiation (generous windows:
                # the first measured pair is only as honest as the
                # slowest path is warm)
                _run_window(sgw.address, sprofile, 0.8, sclients)
                sgw.set_active_workers(1)
                _run_window(sgw.address, sprofile, 0.8, sclients)
                sgw.set_active_workers(gateway_workers)
                for r in range(rounds):
                    rot = sarms[r % 2:] + sarms[:r % 2]
                    for _name, fn, sink in rot:
                        sink.append(fn())
            finally:
                shard_counters = sgw.gateway.gateway_counters()
                sgw.close()
    pairs = [round(n / o, 3) for o, n in zip(qps_one, qps_all) if o]
    shard_pairs = [round(n / o, 3)
                   for o, n in zip(qps_one_worker, qps_nworker) if o]
    pct = all_hist.percentiles()
    if sharded:
        merged = dict(gw.gateway.gateway_counters())
        for k, v in shard_counters.items():
            if isinstance(v, (int, float)):
                merged[k] = merged.get(k, 0) + v
    else:
        merged = counters.snapshot()
    return {
        "replicas": replicas,
        "clients": clients,
        "obs_dim": obs_dim,
        "work_us": work_us,
        "rounds": rounds,
        "window_s": round(window_s, 3),
        "episode_len": episode_len,
        "gateway_workers": gateway_workers,
        "client_procs": client_procs,
        "gateway_qps": round(float(np.median(qps_all)), 2),
        "gateway_qps_1replica": round(float(np.median(qps_one)), 2),
        "gateway_qps_1worker": (
            round(float(np.median(qps_one_worker)), 2)
            if qps_one_worker else None
        ),
        "gateway_qps_nworker": (
            round(float(np.median(qps_nworker)), 2)
            if qps_nworker else None
        ),
        "shard_profile": (
            {"work_us": shard_work_us, "obs_dim": shard_obs_dim,
             "clients": int(shard_clients or min(clients, 12))}
            if sharded else None
        ),
        "gateway_p50_ms": pct["p50_ms"],
        "gateway_p99_ms": pct["p99_ms"],
        "gateway_scale_x": (
            round(float(np.median(pairs)), 3) if pairs else None
        ),
        "gateway_shard_x": (
            round(float(np.median(shard_pairs)), 3)
            if shard_pairs else None
        ),
        "pair_ratios": pairs,
        "shard_pair_ratios": shard_pairs,
        "gateway_counters": {
            k: v for k, v in merged.items()
            if k.startswith("gateway_")
        },
        "stages": {
            k: v for k, v in timer.summary().items()
            if k in ("gw_route", "gw_forward", "gw_reply")
        },
    }


#: default labelled traffic mix (``label:weight:episode_len:think_us``):
#: a steady closed-loop majority, a bursty short-episode tail (admission
#: churn), and a slow-cadence scenario pacing its steps — the
#: multi-scenario workload the single-shape headline never saw.
DEFAULT_MIX = "steady:4:32:0,bursty:2:4:0,slow:2:32:3000"


def parse_mix(spec, obs_dim):
    """``label:weight[:episode_len[:think_us]]`` comma list ->
    :class:`RequestProfile` list."""
    profiles = []
    for part in spec.split(","):
        fields = part.strip().split(":")
        if not fields or not fields[0]:
            raise ValueError(f"bad mix entry {part!r}")
        label = fields[0]
        weight = float(fields[1]) if len(fields) > 1 else 1.0
        episode_len = int(fields[2]) if len(fields) > 2 else 32
        think_us = int(fields[3]) if len(fields) > 3 else 0
        profiles.append(RequestProfile(
            obs_dim, episode_len, scenario=label, weight=weight,
            think_us=think_us,
        ))
    return profiles


def measure_mix(seconds=12.0, clients=8, model="linear", *, obs_dim=8,
                mix=None, rounds=3, slots=None, seed=0, tick_ms=1.0,
                episode_len=32):
    """The ``--scenario-mix`` arm (docs/scenarios.md): the SAME
    batched server and the SAME client loop as the legacy arm, driven
    by a weighted set of labelled :class:`RequestProfile` shapes
    instead of one — per-scenario QPS/p50/p99 plus the union
    ``serve_mix_p99_ms`` headline, the tail latency a realistic
    multi-scenario workload actually observes."""
    from blendjax.serve.server import start_server_thread
    from blendjax.utils.timing import EventCounters, StageTimer

    profiles = (mix if isinstance(mix, list)
                else parse_mix(mix or DEFAULT_MIX, obs_dim))
    slots = slots or max(2 * clients, 16)
    window_s = max(0.5, seconds / max(rounds, 1))
    f_model, _ = _build_models(
        model, obs_dim=obs_dim, d_model=64, n_heads=4, n_layers=2,
        slots=slots, length=64, seed=seed, int8=False,
    )
    timer = StageTimer()
    handle = start_server_thread(
        f_model, counters=EventCounters(), timer=timer, tick_ms=tick_ms,
    )
    qps_rounds = []
    union = LatencyHistogram()
    per = {}  # label -> [count_total, hist]
    try:
        _warm_buckets(handle.server, clients)
        _run_window(handle.address, profiles, 0.3, clients)
        for _ in range(rounds):
            rate, hist, by_scen = _run_window(
                handle.address, profiles, window_s, clients,
            )
            qps_rounds.append(rate)
            union.merge(hist)
            for label, (cnt, h) in by_scen.items():
                rec = per.setdefault(label, [0, LatencyHistogram()])
                rec[0] += cnt
                rec[1].merge(h)
    finally:
        handle.close()
    pct = union.percentiles()
    per_scenario = {}
    for label, (cnt, h) in sorted(per.items()):
        p = h.percentiles()
        per_scenario[label] = {
            "qps": round(cnt / (rounds * window_s), 2),
            "p50_ms": p["p50_ms"],
            "p99_ms": p["p99_ms"],
        }
    return {
        "model": model,
        "clients": clients,
        "rounds": rounds,
        "window_s": round(window_s, 3),
        "mix": [
            {"scenario": p.scenario, "weight": p.weight,
             "episode_len": p.episode_len, "think_us": p.think_us}
            for p in profiles
        ],
        "serve_mix_qps": round(float(np.median(qps_rounds)), 2),
        "serve_mix_p50_ms": pct["p50_ms"],
        "serve_mix_p99_ms": pct["p99_ms"],
        "per_scenario": per_scenario,
        "stages": {
            k: v for k, v in timer.summary().items()
            if k in ("queue_wait", "batch_assemble", "compute", "reply")
        },
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seconds", type=float, default=18.0,
                    help="total timed budget across all windows")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--model", default="seqformer",
                    choices=("linear", "policy", "seqformer"))
    ap.add_argument("--obs-dim", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--length", type=int, default=64)
    ap.add_argument("--episode-len", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--no-int8", dest="int8", action="store_false")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gateway", action="store_true",
                    help="fleet bench: N replica processes behind a "
                         "ServeGateway, 1-replica vs N-replica windows")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--gateway-workers", type=int, default=1,
                    help="gateway bench: >1 runs the SHARDED gateway "
                         "(N worker processes behind one front) and "
                         "adds interleaved 1-worker windows — "
                         "gateway_shard_x at the median pair")
    ap.add_argument("--client-procs", type=int, default=0,
                    help="spread each window's bench clients over this "
                         "many processes (0 = threads in-process); GIL "
                         "isolation on small boxes, recorded in the "
                         "artifact for before/after comparison")
    ap.add_argument("--work-us", type=float, default=2000,
                    help="gateway bench: per-row replica compute "
                         "stand-in (sleep-based, linear model)")
    ap.add_argument("--shard-work-us", type=float, default=500,
                    help="shard-phase fleet's per-row work (light, so "
                         "the data-plane hop dominates the window)")
    ap.add_argument("--shard-obs-dim", type=int, default=128,
                    help="shard-phase fleet's observation width (fat, "
                         "so the per-message wire cost is visible)")
    ap.add_argument("--shard-clients", type=int, default=None,
                    help="shard-phase client count (default: "
                         "min(--clients, 12) — on small CI boxes more "
                         "client threads just saturate the core and "
                         "flatten both arms to the same CPU ceiling)")
    ap.add_argument("--scenario-mix", nargs="?", const=DEFAULT_MIX,
                    default=None, metavar="L:W[:EP[:THINK_US]],...",
                    help="labelled traffic-mix arm (docs/scenarios.md): "
                         "weighted request profiles over one batched "
                         "server; reports per-scenario QPS/p99 and the "
                         "serve_mix_p99_ms union headline")
    args = ap.parse_args(argv)
    if args.scenario_mix is not None:
        rec = measure_mix(
            seconds=args.seconds, clients=args.clients,
            model=args.model, obs_dim=args.obs_dim,
            mix=args.scenario_mix, rounds=args.rounds or 3,
            slots=args.slots, seed=args.seed,
        )
        line = {
            "metric": "serve_mix_p99_ms",
            "value": rec["serve_mix_p99_ms"],
            "unit": "ms",
            "phase": "serve_mix_bench",
            **rec,
        }
        print(json.dumps(line), flush=True)
        return 0
    if args.gateway:
        rec = measure_gateway(
            seconds=args.seconds, clients=args.clients,
            replicas=args.replicas, obs_dim=args.obs_dim,
            work_us=args.work_us, episode_len=args.episode_len,
            rounds=args.rounds or 3, seed=args.seed,
            gateway_workers=args.gateway_workers,
            client_procs=args.client_procs,
            shard_work_us=args.shard_work_us,
            shard_obs_dim=args.shard_obs_dim,
            shard_clients=args.shard_clients,
        )
        line = {
            "metric": "gateway_qps",
            "value": rec["gateway_qps"],
            "unit": "req/sec",
            "phase": "gateway_bench",
            **rec,
        }
        print(json.dumps(line), flush=True)
        return 0
    rec = measure(
        seconds=args.seconds, clients=args.clients, model=args.model,
        obs_dim=args.obs_dim, d_model=args.d_model,
        n_heads=args.n_heads, n_layers=args.n_layers, slots=args.slots,
        length=args.length, episode_len=args.episode_len,
        rounds=args.rounds, int8=args.int8, seed=args.seed,
    )
    line = {
        "metric": "serve_qps",
        "value": rec["serve_qps"],
        "unit": "req/sec",
        "phase": "serve_bench",
        **rec,
    }
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
