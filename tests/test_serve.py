"""Policy-serving inference tier tests (docs/serving.md).

The load-bearing ones are the parity locks: per-row-position batched
``decode_step`` must equal per-episode serial decode at heterogeneous
timesteps (with and without ``window`` ring caches) — one batched
compute serving many episodes is a scheduling choice, not a numerics
choice — and the exactly-once chaos tests: every submitted request
yields exactly one applied decode however the wire mangles it, and a
SIGKILL'd server respawned by ``FleetWatchdog`` lets clients resume
after ``reset()``.
"""

import functools
import json
import threading
import time

import numpy as np
import pytest

from blendjax.btt.faults import FaultPolicy
from blendjax.utils.timing import (
    SERVE_EVENTS,
    SERVE_STAGES,
    EventCounters,
    StageTimer,
)


def _serve_counts(counters):
    return {k: v for k, v in counters.snapshot().items()
            if k.startswith("serve_")}


# ---------------------------------------------------------------------------
# per-row-position decode: the tentpole model change
# ---------------------------------------------------------------------------


def _serial_decode(params, episodes, length, window, jit=True):
    """Per-episode scalar-position decode — the reference the batched
    per-row path must match."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer

    step = functools.partial(
        seqformer.decode_step, compute_dtype=jnp.float32, window=window
    )
    if jit:
        step = jax.jit(step)
    out = []
    for ep in episodes:
        cache = seqformer.init_cache(
            params, 1, dtype=jnp.float32, length=length
        )
        preds = []
        for t in range(len(ep)):
            p, cache = step(params, cache, jnp.asarray(ep[t][None]))
            preds.append(np.asarray(p[0]))
        out.append(np.stack(preds))
    return out


def _batched_decode(params, episodes, length, window):
    """One per-row cache over every episode, stepped in sub-batches of
    whichever episodes still have observations — exactly the serving
    tier's gather -> decode_step -> scatter kernel."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer

    n = len(episodes)
    cache = seqformer.init_cache(
        params, n, dtype=jnp.float32, length=length, per_row=True
    )

    @jax.jit
    def step(params, cache, idx, obs):
        rows = {
            "pos": cache["pos"][idx],
            "k": [k[idx] for k in cache["k"]],
            "v": [v[idx] for v in cache["v"]],
        }
        pred, new = seqformer.decode_step(
            params, rows, obs, compute_dtype=jnp.float32, window=window
        )
        cache = {
            "pos": cache["pos"].at[idx].set(new["pos"]),
            "k": [c.at[idx].set(nk)
                  for c, nk in zip(cache["k"], new["k"])],
            "v": [c.at[idx].set(nv)
                  for c, nv in zip(cache["v"], new["v"])],
        }
        return pred, cache

    got = [[] for _ in range(n)]
    for t in range(max(len(ep) for ep in episodes)):
        idx = np.asarray([i for i in range(n) if t < len(episodes[i])])
        obs = jnp.asarray(np.stack([episodes[i][t] for i in idx]))
        pred, cache = step(params, cache, jnp.asarray(idx), obs)
        for j, i in enumerate(idx):
            got[i].append(np.asarray(pred[j]))
    return [np.stack(p) for p in got], cache


@pytest.mark.parametrize(
    "kwargs,window",
    [
        (dict(), None),
        (dict(), 4),
        (dict(pos_encoding="rope"), None),
        (dict(pos_encoding="rope"), 4),
        (dict(n_kv_heads=2), None),
    ],
    ids=["learned", "learned-windowed", "rope", "rope-windowed", "gqa"],
)
def test_per_row_decode_matches_per_episode_serial(kwargs, window):
    """THE serving correctness bar: batched decode with per-row
    positions == per-episode serial decode, at heterogeneous episode
    lengths (rows sit at different timesteps every tick), with and
    without ``window`` ring caches.  f32 end to end; the only
    difference allowed is batched-matmul accumulation order (~1e-6)."""
    import jax

    from blendjax.models import seqformer

    params = seqformer.init(
        jax.random.PRNGKey(0), obs_dim=5, d_model=32, n_heads=4,
        n_layers=2, max_len=32, **kwargs,
    )
    rng = np.random.default_rng(0)
    episodes = [
        rng.standard_normal((t, 5)).astype(np.float32)
        for t in (7, 3, 5, 1)
    ]
    length = 16 if window is None else window
    want = _serial_decode(params, episodes, length, window)
    got, _ = _batched_decode(params, episodes, length, window)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)


def test_per_row_cache_shapes_and_reset_masks_stale_rows():
    """``init_cache(per_row=True)`` gives a (B,) position vector, and
    rewinding ONE row's position to 0 is a full episode reset: the
    previous tenant's k/v rows sit at now-negative slot positions and
    never attend (no zeroing needed — the slot-position mask is the
    eviction)."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer

    params = seqformer.init(
        jax.random.PRNGKey(0), obs_dim=5, d_model=32, n_heads=4,
        n_layers=2, max_len=32,
    )
    cache = seqformer.init_cache(
        params, 3, dtype=jnp.float32, length=8, per_row=True
    )
    assert cache["pos"].shape == (3,)
    rng = np.random.default_rng(1)
    old_ep = rng.standard_normal((5, 5)).astype(np.float32)
    # burn episode history into row 1
    for t in range(5):
        obs = jnp.asarray(np.stack([old_ep[t]] * 3))
        _, cache = seqformer.decode_step(
            params, cache, obs, compute_dtype=jnp.float32
        )
    # reset row 1 only, then serve a fresh episode on it
    cache["pos"] = cache["pos"].at[1].set(0)
    new_ep = rng.standard_normal((3, 5)).astype(np.float32)
    fresh = seqformer.init_cache(
        params, 1, dtype=jnp.float32, length=8
    )
    for t in range(3):
        obs = jnp.asarray(np.stack([new_ep[t]] * 3))
        p, cache = seqformer.decode_step(
            params, cache, obs, compute_dtype=jnp.float32
        )
        ref, fresh = seqformer.decode_step(
            params, fresh, jnp.asarray(new_ep[t][None]),
            compute_dtype=jnp.float32,
        )
        np.testing.assert_allclose(
            np.asarray(p[1]), np.asarray(ref[0]), atol=1e-5, rtol=1e-5
        )


# ---------------------------------------------------------------------------
# batched prefill admission (ISSUE-11): one teacher-forced pass == T
# serial decode steps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kwargs,window",
    [
        (dict(), None),
        (dict(), 4),
        (dict(pos_encoding="rope"), None),
        (dict(pos_encoding="rope"), 4),
        (dict(n_kv_heads=2), None),
    ],
    ids=["learned", "learned-windowed", "rope", "rope-windowed", "gqa"],
)
def test_prefill_admission_matches_serial_decode(kwargs, window):
    """Batched prefill (ONE teacher-forced pass filling the slot's KV
    rows) must agree with T serial ``decode_step``s across the PR-10
    parity matrix: the prefill prediction equals the T'th serial
    prediction, and every LATER step decodes identically — the cache
    the prefill wrote is byte-equivalent to the serially-built one."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel

    params = seqformer.init(
        jax.random.PRNGKey(0), obs_dim=5, d_model=32, n_heads=4,
        n_layers=2, max_len=32, **kwargs,
    )
    rng = np.random.default_rng(2)
    ep = rng.standard_normal((9, 5)).astype(np.float32)
    t0 = 5
    want = _serial_decode(params, [ep], 16, window)[0]
    model = SeqFormerModel(params, slots=3, length=16, window=window,
                           compute_dtype=jnp.float32)
    pred = model.prefill_rows(np.asarray([1]), ep[:t0])
    np.testing.assert_allclose(pred, want[t0 - 1], atol=1e-5, rtol=1e-5)
    for t in range(t0, len(ep)):
        got = model.step_rows(np.asarray([1]), ep[t][None])[0]
        np.testing.assert_allclose(got, want[t], atol=1e-5, rtol=1e-5)


def _prefill_case(kind):
    """(params, prefixes (2, T0, ...), ring length, window)."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer

    rng = np.random.default_rng(4)
    if kind == "latent":
        config = dict(
            hidden_size=32, num_attention_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            rope_theta=10000, rope_scaling=None, num_hidden_layers=2,
            first_k_dense_replace=1, intermediate_size=64,
            moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
            routed_scaling_factor=2.5, num_shared_experts=1, vocab_size=64)
        params = seqformer.init_token_model(
            jax.random.PRNGKey(0), config, dtype=jnp.float32)
        return params, rng.integers(0, 64, (2, 7)).astype(np.int32), 16, None
    params = seqformer.init(
        jax.random.PRNGKey(0), obs_dim=5, d_model=32, n_heads=4,
        n_layers=2, max_len=32)
    prefix = rng.standard_normal((2, 7, 5)).astype(np.float32)
    # windowed-wrap: 7 positions through a ring of 4 keep 3..6 at slots
    # 3, 0, 1, 2
    return (params, prefix) + ((4, 4) if kind == "windowed-wrap"
                               else (16, None))


@pytest.mark.parametrize("kind", ["plain", "windowed-wrap", "latent"])
def test_prefill_writes_a_scalar_cache_and_pool_rows_alike(kind):
    """``seqformer.prefill`` is the one writer of a prefix: into a
    scalar-position cache (what ``rollout`` does) and into chosen rows
    of a per-row pool (what the server does) it leaves the same bytes
    and the same last prediction, what T0 serial ``decode_step``s leave
    (to rounding), and every row not named is bit-identical."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer

    params, prefix, length, window = _prefill_case(kind)
    t0 = prefix.shape[1]
    names = ["kv"] if kind == "latent" else ["k", "v"]

    def marked(cache):  # so that an unwritten position shows
        return {n: (v if n == "pos" else [jnp.full_like(a, 7.0) for a in v])
                for n, v in cache.items()}

    fill = functools.partial(seqformer.prefill, compute_dtype=jnp.float32,
                             window=window)
    scalar = marked(seqformer.init_cache(
        params, 2, dtype=jnp.float32, length=length))
    preds_a, a = fill(params, scalar, prefix)
    pool = marked(seqformer.init_cache(
        params, 5, dtype=jnp.float32, length=length, per_row=True))
    pool["pos"] = jnp.arange(5, dtype=jnp.int32) + 20
    rows = np.asarray([3, 1])
    preds_b, b = fill(params, pool, prefix, jnp.asarray(rows))
    others = np.asarray([0, 2, 4])

    np.testing.assert_array_equal(preds_a[:, -1], preds_b[:, -1])
    assert a["pos"].shape == () and int(a["pos"]) == t0
    np.testing.assert_array_equal(b["pos"], [20, t0, 22, t0, 24])
    kept = (np.arange(min(t0, length)) + t0 - min(t0, length)) % length
    for name in names:
        for layer_a, layer_b, before in zip(a[name], b[name], pool[name]):
            np.testing.assert_array_equal(np.asarray(layer_b)[rows], layer_a)
            np.testing.assert_array_equal(np.asarray(layer_b)[others],
                                          np.asarray(before)[others])
            assert not np.any(np.asarray(layer_a)[:, kept] == 7.0)
            unwritten = np.setdiff1d(np.arange(length), kept)
            assert np.all(np.asarray(layer_a)[:, unwritten] == 7.0)

    # T0 serial steps through a scalar cache of the same ring
    serial = seqformer.init_cache(params, 2, dtype=jnp.float32, length=length)
    step = jax.jit(functools.partial(
        seqformer.decode_step, compute_dtype=jnp.float32, window=window))
    for t in range(t0):
        pred, serial = step(params, serial, jnp.asarray(prefix[:, t]))
    np.testing.assert_allclose(preds_a[:, -1], pred, atol=2e-5, rtol=1e-5)
    for name in names:
        for layer_a, layer_s in zip(a[name], serial[name]):
            np.testing.assert_allclose(np.asarray(layer_a)[:, kept],
                                       np.asarray(layer_s)[:, kept],
                                       atol=2e-5, rtol=1e-5)


def test_prefill_reset_end_to_end_and_validation():
    """The wire path: ``reset(prefix=...)`` admits mid-sequence (pred/
    pos in the reply, ``serve_prefills`` counted), and malformed or
    unservable prefixes error actionably with the slot RELEASED."""
    from blendjax.serve import (
        LinearModel,
        PolicyModel,
        ServeClient,
        start_server_thread,
    )

    counters = EventCounters()
    with start_server_thread(
        LinearModel(obs_dim=4, slots=1, seed=0), counters=counters,
    ) as h:
        c = ServeClient(h.address, fault_policy=FaultPolicy(max_retries=0))
        rng = np.random.default_rng(1)
        prefix = rng.standard_normal((5, 4)).astype(np.float32)
        ref = LinearModel(obs_dim=4, slots=1, seed=0)
        reply = c.reset(prefix=prefix)
        assert reply["pos"] == 5
        np.testing.assert_allclose(
            reply["pred"], ref.prefill_rows(np.asarray([0]), prefix)
        )
        r = c.step(prefix[0])
        assert r["pos"] == 5
        assert c.close_episode()
        # a bad prefix shape errors AND releases the (only) slot
        with pytest.raises(RuntimeError, match="prefix shape"):
            c.reset(prefix=np.zeros((3, 9), np.float32))
        c.reset(prefix=prefix)  # the slot came back
        assert c.close_episode()
        assert _serve_counts(counters)["serve_prefills"] == 2
        c.close()
    # stateless models refuse prefill admission actionably
    import jax

    from blendjax.models import policy

    params = policy.init(jax.random.PRNGKey(0), 4, 3)
    with start_server_thread(PolicyModel(params, 4)) as h:
        c = ServeClient(h.address, fault_policy=FaultPolicy(max_retries=0))
        with pytest.raises(RuntimeError, match="stateless"):
            c.reset(prefix=np.zeros((3, 4), np.float32))
        c.close()


# ---------------------------------------------------------------------------
# multi-model hosting (ISSUE-11)
# ---------------------------------------------------------------------------


def test_multi_model_server_per_model_pools_and_routing():
    """One server hosting two models: requests route by the envelope's
    model id (per-seed weight witness), each model owns its OWN slot
    pool (one model's exhaustion cannot deny the other), and an unknown
    model id errors actionably."""
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    obs = np.arange(4, dtype=np.float32)
    with start_server_thread({
        "a": LinearModel(obs_dim=4, slots=1, seed=0),
        "b": LinearModel(obs_dim=4, slots=2, seed=7),
    }) as h:
        ca = ServeClient(h.address, model="a",
                         fault_policy=FaultPolicy(max_retries=0))
        cb = ServeClient(h.address, model="b",
                         fault_policy=FaultPolicy(max_retries=0))
        hello = ca.hello()
        assert set(hello["models"]) == {"a", "b"}
        ca.reset()
        cb.reset()
        wa = LinearModel(obs_dim=4, slots=1, seed=0).w
        wb = LinearModel(obs_dim=4, slots=2, seed=7).w
        np.testing.assert_allclose(ca.step(obs)["pred"], obs @ wa)
        np.testing.assert_allclose(cb.step(obs)["pred"], obs @ wb)
        # model a is full (1 slot); model b still admits
        ca2 = ServeClient(h.address, model="a",
                          fault_policy=FaultPolicy(max_retries=0))
        with pytest.raises(RuntimeError, match="no free episode slot"):
            ca2.reset()
        cb2 = ServeClient(h.address, model="b")
        cb2.reset()
        bogus = ServeClient(h.address, model="nope",
                            fault_policy=FaultPolicy(max_retries=0))
        with pytest.raises(RuntimeError, match="unknown model"):
            bogus.reset()
        for c in (ca, cb, ca2, cb2, bogus):
            c.close()


def test_multi_model_single_workload_replies_identical():
    """The ISSUE-11 parity bar: a multi-model server hosting ONE model
    answers a single-model workload with replies identical to a plain
    single-model server — same keys, same values, same bytes in the
    prediction rows."""
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    def run_workload(address):
        c = ServeClient(address)
        out = []
        obs = np.linspace(-1, 1, 4).astype(np.float32)
        out.append(("hello", c.hello()))
        c.reset()
        out.append(("reset", {"slot": c.slot, "episode": c.episode}))
        for t in range(3):
            out.append(("step", c.step(obs + t)))
        out.append(("close", {"closed": c.close_episode()}))
        c.close()
        return out

    with start_server_thread(LinearModel(obs_dim=4, slots=2, seed=0)) as h:
        single = run_workload(h.address)
    with start_server_thread(
        {"linear": LinearModel(obs_dim=4, slots=2, seed=0)}
    ) as h:
        multi = run_workload(h.address)
    assert len(single) == len(multi)
    for (ks, vs), (km, vm) in zip(single, multi):
        assert ks == km
        assert set(vs) == set(vm), (ks, set(vs), set(vm))
        for key in vs:
            if isinstance(vs[key], np.ndarray):
                assert vs[key].tobytes() == vm[key].tobytes(), (ks, key)
            elif key not in ("pid", "shm"):
                # pid and the shm endpoint advertisement are process
                # identity, not workload semantics
                assert vs[key] == vm[key], (ks, key)


# ---------------------------------------------------------------------------
# PolicyServer: batching, slots, counters
# ---------------------------------------------------------------------------


def test_linear_server_end_to_end_counters_and_stages():
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    counters, timer = EventCounters(), StageTimer()
    with start_server_thread(
        LinearModel(obs_dim=4, slots=2, seed=0),
        counters=counters, timer=timer,
    ) as h:
        c = ServeClient(h.address)
        hello = c.hello()
        assert hello["model"] == "linear" and hello["slots"] == 2
        c.reset()
        obs = np.arange(4, dtype=np.float32)
        r0, r1 = c.step(obs), c.step(obs)
        assert (r0["pos"], r1["pos"]) == (0, 1)
        np.testing.assert_allclose(r1["pred"], r0["pred"] + 1.0)
        # slot exhaustion: 1 live + 2 more resets -> second one denied
        c2 = ServeClient(h.address, fault_policy=FaultPolicy(max_retries=0))
        c2.reset()
        with pytest.raises(RuntimeError, match="no free episode slot"):
            c2.rpc("reset")
        # close frees the slot; the next reset succeeds
        assert c.close_episode()
        c2.rpc("reset")
        # stepping an unknown slot errors actionably
        with pytest.raises(RuntimeError, match="unknown episode slot"):
            c2.step(obs, slot=99)
        # the reply counter lands AFTER the socket send, so the client
        # can observe its reply a beat before the server's increment —
        # wait out that window before asserting the exact invariant
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            snap = _serve_counts(counters)
            if snap["serve_requests"] == (
                snap["serve_replies"] + snap.get("serve_dup_inflight", 0)
            ):
                break
            time.sleep(0.01)
        assert snap["serve_slot_denied"] == 1
        assert snap["serve_errors"] >= 2  # denial + unknown slot
        assert snap["serve_resets"] == 3
        assert snap["serve_batches"] >= 2
        # every admitted request is answered exactly once — except a
        # duplicate of a still-queued request, which is dropped at
        # admission and answered by the original's reply (a loaded CI
        # box can push a client into that retry)
        assert snap["serve_requests"] == (
            snap["serve_replies"] + snap.get("serve_dup_inflight", 0)
        )
        summary = timer.summary()
        for stage in SERVE_STAGES:
            assert summary[stage]["count"] > 0, stage
        c.close()
        c2.close()


def test_slot_ttl_eviction():
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    counters = EventCounters()
    with start_server_thread(
        LinearModel(obs_dim=4, slots=1, seed=0),
        counters=counters, slot_ttl_s=0.2,
    ) as h:
        c1 = ServeClient(h.address)
        c1.reset()
        time.sleep(0.3)
        # the only slot is idle past the ttl: a new episode evicts it
        c2 = ServeClient(h.address)
        c2.reset()
        assert _serve_counts(counters)["serve_evictions"] == 1
        # the evicted episode's slot was REASSIGNED: the stale client's
        # lease refuses the step instead of advancing the new tenant
        with pytest.raises(RuntimeError, match="stale episode lease"):
            c1.step(np.zeros(4, np.float32))
        # ... and its stale close cannot kill the new episode either
        assert not c1.close_episode()
        c2.step(np.zeros(4, np.float32))
        c1.close()
        c2.close()


def test_seqformer_server_concurrent_episodes_match_serial():
    """End-to-end world-model serving: concurrent episode clients at
    heterogeneous lengths through the batching server equal per-episode
    serial decode — the tier-level restatement of the kernel parity."""
    import jax

    from blendjax.models import seqformer
    from blendjax.serve import (
        SeqFormerModel,
        ServeClient,
        start_server_thread,
    )

    params = seqformer.init(
        jax.random.PRNGKey(0), obs_dim=5, d_model=32, n_heads=4,
        n_layers=2, max_len=32,
    )
    rng = np.random.default_rng(1)
    episodes = [
        rng.standard_normal((t, 5)).astype(np.float32) for t in (6, 3, 5)
    ]
    want = _serial_decode(params, episodes, 16, None)
    counters = EventCounters()
    with start_server_thread(
        SeqFormerModel(params, slots=4, length=16), counters=counters,
    ) as h:
        outs = [[] for _ in episodes]

        def run(i):
            c = ServeClient(h.address, timeoutms=20000)
            c.reset()
            for t in range(len(episodes[i])):
                outs[i].append(c.step(episodes[i][t])["pred"])
            c.close_episode()
            c.close()

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(episodes))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    for i, w in enumerate(want):
        np.testing.assert_allclose(
            np.stack(outs[i]), w, atol=1e-5, rtol=1e-5
        )
    assert _serve_counts(counters)["serve_batches"] >= 3


def test_policy_server_stateless_greedy_logits():
    import jax

    from blendjax.models import policy
    from blendjax.serve import PolicyModel, ServeClient, start_server_thread

    params = policy.init(jax.random.PRNGKey(0), 6, 3)
    counters = EventCounters()
    with start_server_thread(PolicyModel(params, 6),
                             counters=counters) as h:
        c = ServeClient(h.address)
        assert c.hello()["slots"] == 0
        assert c.reset() == -1  # stateless: no slot pool
        obs = np.linspace(-1, 1, 6).astype(np.float32)
        pred = c.step(obs)["pred"]
        want = np.asarray(policy.logits(params, obs[None]))[0]
        np.testing.assert_allclose(pred, want, atol=1e-5, rtol=1e-5)
        # stateless episodes still reconcile: the real close counts,
        # a duplicate close of the same episode does not
        assert c.stats()["live_episodes"] == 1
        assert c.close_episode()
        stale = ServeClient(h.address)
        stale.slot, stale.episode = -1, 999  # never admitted
        assert not stale.close_episode()
        snap = _serve_counts(counters)
        assert snap["serve_closes"] == 1 == snap["serve_resets"]
        stale.close()
        c.close()


# ---------------------------------------------------------------------------
# int8 serving parity (satellite)
# ---------------------------------------------------------------------------


def _trained_seqformer(key, obs_dim=5, steps=20):
    import jax
    import jax.numpy as jnp
    import optax

    from blendjax.models import seqformer
    from blendjax.models.train import TrainState, make_train_step

    params = seqformer.init(
        key, obs_dim=obs_dim, d_model=32, n_heads=4, n_layers=2,
        max_len=32,
    )
    batch = seqformer.make_episode_batch(
        jax.random.normal(jax.random.PRNGKey(9), (4, 17, obs_dim),
                          jnp.float32)
    )
    state = TrainState.create(params, optax.adam(1e-2))
    step = make_train_step(
        lambda p, b: seqformer.loss_fn(p, b, compute_dtype=jnp.float32),
        optax.adam(1e-2),
    )
    for _ in range(steps):
        state, _ = step(state, batch)
    return jax.device_get(state.params)


def test_int8_served_predictions_track_float():
    """The int8 serving path (quantize_seqformer through the same
    batched per-row decode) agrees with the float server within the
    tolerance the ops/quant tests use on a TRAINED model (5% of the
    output scale — random weights overstate quantization error)."""
    import jax

    from blendjax.serve import (
        SeqFormerModel,
        ServeClient,
        start_server_thread,
    )

    params = _trained_seqformer(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    ep = rng.standard_normal((6, 5)).astype(np.float32)

    def serve_episode(model):
        with start_server_thread(model) as h:
            c = ServeClient(h.address, timeoutms=20000)
            c.reset()
            preds = [c.step(ep[t])["pred"] for t in range(len(ep))]
            c.close_episode()
            c.close()
        return np.stack(preds)

    ref = serve_episode(SeqFormerModel(params, slots=2, length=16))
    got = serve_episode(
        SeqFormerModel(params, slots=2, length=16, int8=True)
    )
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    assert err < 0.05 * max(scale, 1.0), (err, scale)


def test_int8_policy_logits_track_float():
    import jax

    from blendjax.models import policy
    from blendjax.ops.quant import quantize_policy

    params = policy.init(jax.random.PRNGKey(1), 6, 4)
    obs = np.random.default_rng(0).standard_normal((16, 6)).astype(
        np.float32
    )
    ref = np.asarray(policy.logits(params, obs))
    got = np.asarray(policy.logits(quantize_policy(params), obs))
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    assert err < 0.05 * max(scale, 1.0), (err, scale)


def test_malformed_requests_error_but_server_survives():
    """Garbage must come back as error replies, never kill the serving
    thread: unknown command, step without obs, ragged obs shape."""
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    counters = EventCounters()
    with start_server_thread(
        LinearModel(obs_dim=4, slots=2, seed=0), counters=counters,
    ) as h:
        c = ServeClient(h.address, fault_policy=FaultPolicy(max_retries=0))
        c.reset()
        with pytest.raises(RuntimeError, match="unknown serve command"):
            c.rpc("frobnicate")
        with pytest.raises(RuntimeError, match="obs"):
            c.rpc("step", {"slot": c.slot, "episode": c.episode})
        with pytest.raises(RuntimeError, match="obs shape"):
            c.rpc("step", {"slot": c.slot, "episode": c.episode,
                           "obs": np.zeros(7, np.float32)},
                  raw_buffers=True)
        # ... and the episode still serves afterwards
        r = c.step(np.zeros(4, np.float32))
        assert r["pos"] == 0
        assert _serve_counts(counters)["serve_errors"] == 3
        # undecodable FRAMES (a garbling proxy, a rogue peer) must not
        # kill the serve loop either: raw garbage, then a real step
        import zmq

        rogue = zmq.Context.instance().socket(zmq.DEALER)
        rogue.setsockopt(zmq.LINGER, 0)
        rogue.connect(h.address)
        rogue.send_multipart([b"", b"not-pickle-at-all"])
        rogue.close(0)
        r = c.step(np.zeros(4, np.float32))
        assert r["pos"] == 1
        c.close()


# ---------------------------------------------------------------------------
# exactly-once through wire faults (satellite)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.parametrize("transport", ["tcp", "shm"])
def test_exactly_once_through_drop_dup_and_stall(transport):
    """Wire faults between ServeClient and PolicyServer must each yield
    EXACTLY one applied step per submitted request — the LinearModel's
    position counter rides every prediction, so a double-applied step
    shifts every later value and the reference comparison catches it.
    Parametrized over BOTH wires (ISSUE-12): the ``tcp`` arm injects at
    the TCP chunk layer (ChaosProxy, shm upgrade pinned off), the
    ``shm`` arm at the ring frame layer (ShmChaos) — the shared
    ``btt/rpc.py`` discipline is locked on each, not just the one it
    was written against."""
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    counters = EventCounters()
    model = LinearModel(obs_dim=4, slots=2, seed=0)
    ref = LinearModel(obs_dim=4, slots=2, seed=0)
    obs = np.arange(4, dtype=np.float32)
    with start_server_thread(model, counters=counters) as h:
        if transport == "tcp":
            _serve_chaos_tcp_arm(h, counters, ref, obs)
        else:
            _serve_chaos_shm_arm(h, counters, ref, obs)


def _serve_chaos_tcp_arm(h, counters, ref, obs):
    from blendjax.btt.chaos import ChaosProxy
    from blendjax.serve import ServeClient

    with ChaosProxy(h.address) as proxy:
        client = ServeClient(
            proxy.address,
            fault_policy=FaultPolicy(
                max_retries=4, backoff_base=0.02, backoff_max=0.1,
                circuit_threshold=0, seed=1,
            ),
            counters=counters, timeoutms=400, shm=False,
        )
        client.reset()
        ref.reset_rows(np.asarray([0]))
        preds = []
        for t in range(20):
            if t == 5:
                proxy.drop_next("down")   # lose a reply -> retry
            if t == 9:
                proxy.dup_next("up")      # duplicate a request
            if t == 13:
                proxy.stall()

                def unstall():
                    time.sleep(0.6)  # past the 400 ms attempt
                    proxy.resume()

                threading.Thread(target=unstall, daemon=True).start()
            preds.append(client.step(obs)["pred"])
        want = [ref.step_rows(np.asarray([0]), obs[None])[0]
                for _ in range(20)]
        np.testing.assert_allclose(np.stack(preds), np.stack(want))
        snap = counters.snapshot()
        # the faults actually happened and were healed by the
        # exactly-once machinery, not by luck
        assert snap.get("retries", 0) >= 2
        assert (
            snap.get("serve_cache_hits", 0)
            + snap.get("serve_dup_inflight", 0)
        ) >= 1
        client.close()


def _serve_chaos_shm_arm(h, counters, ref, obs):
    """Frame-layer faults on the upgraded channel: a duplicated request
    (stays on shm — reply-cache/in-queue dedupe), then a dropped reply
    whose same-mid retry rides the DEMOTED ZMQ path and is answered
    from the server's reply cache (exactly-once ACROSS the transports
    — the respawn-heal discipline in miniature), then the re-upgrade
    onto a fresh ring generation."""
    from blendjax.btt.shm_rpc import ShmChaos, enabled
    from blendjax.serve import ServeClient

    if not enabled():
        pytest.skip("shm rpc unavailable on this host")
    chaos = ShmChaos(seed=1)
    client = ServeClient(
        h.address,
        fault_policy=FaultPolicy(
            max_retries=4, backoff_base=0.02, backoff_max=0.1,
            circuit_threshold=0, seed=1,
        ),
        counters=counters, timeoutms=400, shm_chaos=chaos,
    )
    client.reset()
    ref.reset_rows(np.asarray([0]))
    preds = []
    for t in range(20):
        if t == 4:
            assert client.transport == "shm", "upgrade never happened"
            chaos.dup_next("up")      # duplicate a request in the ring
        if t == 8:
            chaos.drop_next("down")   # lose a reply -> timeout ->
            #                           demote -> same-mid retry on zmq
        preds.append(client.step(obs)["pred"])
    # the dropped reply demoted the channel: its retry rode ZMQ
    assert client.transport == "tcp"
    want = [ref.step_rows(np.asarray([0]), obs[None])[0]
            for _ in range(20)]
    np.testing.assert_allclose(np.stack(preds), np.stack(want))
    snap = counters.snapshot()
    assert snap.get("retries", 0) >= 1
    assert (
        snap.get("serve_cache_hits", 0)
        + snap.get("serve_dup_inflight", 0)
    ) >= 1, snap
    assert chaos.dropped >= 1 and chaos.duplicated >= 1
    # generation heal: once the (live) server answers on ZMQ and the
    # backoff elapses, the channel re-upgrades onto fresh rings
    time.sleep(1.1)
    for _ in range(3):
        preds.append(client.step(obs)["pred"])
    assert client.transport == "shm", "channel never re-upgraded"
    assert client._chan.generations == 2
    np.testing.assert_allclose(
        np.stack(preds[-3:]),
        np.stack([ref.step_rows(np.asarray([0]), obs[None])[0]
                  for _ in range(3)]),
    )
    client.close()


@pytest.mark.chaos
def test_sigkilled_server_respawned_by_watchdog_resumes_after_reset():
    """The serving tier's crash contract: SIGKILL the server process,
    let ``FleetWatchdog(restart=True)`` respawn it (same command line,
    seed-deterministic weights), and a client resumes after ``reset()``
    — its old slot is gone (the error names it), its new episode serves
    correctly, and the fault counters are pinned."""
    from blendjax.btt.chaos import kill_instance
    from blendjax.btt.watchdog import FleetWatchdog
    from blendjax.serve import ServeClient, ServerProcess

    counters = EventCounters()
    obs = np.arange(4, dtype=np.float32)
    with ServerProcess(model="linear", obs_dim=4, slots=4) as sp:
        with FleetWatchdog(sp, interval=0.2, restart=True):
            client = ServeClient(
                sp.address,
                fault_policy=FaultPolicy(
                    max_retries=1, backoff_base=0.05, backoff_max=0.2,
                    circuit_threshold=0, seed=2,
                ),
                counters=counters, timeoutms=500,
            )
            client.reset()
            first = client.step(obs)
            assert first["pos"] == 0

            kill_instance(sp, 0)
            # steps against the dead (then fresh) server fail with
            # either a transport timeout (server still down) or an
            # unknown-slot error (the watchdog's respawn won the race)
            # — never a silent wrong answer; reset-and-resume recovers
            deadline = time.monotonic() + 30
            recovered = False
            failures = []
            while time.monotonic() < deadline:
                try:
                    client.step(obs)
                except (TimeoutError, RuntimeError) as exc:
                    failures.append(exc)
                    try:
                        client.reset_channel()
                        client.reset(timeout_ms=500)
                        recovered = True
                        break
                    except (TimeoutError, RuntimeError) as exc2:
                        failures.append(exc2)
                        time.sleep(0.1)
            assert recovered, "client never recovered after respawn"
            r = client.step(obs)
            assert r["pos"] == 0  # a FRESH episode on the new server
            np.testing.assert_allclose(r["pred"], first["pred"])
            # the kill was OBSERVED, one way or the other: transport
            # timeouts pinned in the fault counters, or the fresh
            # server's unknown-slot refusal
            snap = counters.snapshot()
            assert failures, "kill was never observed by the client"
            assert snap.get("timeouts", 0) >= 1 or any(
                "episode slot" in str(e) for e in failures
            ), (snap, [str(e) for e in failures])
            client.close()


# ---------------------------------------------------------------------------
# telemetry plane integration
# ---------------------------------------------------------------------------


def test_hub_scrapes_server_remotely_and_locally():
    from blendjax.obs.hub import TelemetryHub
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    counters, timer = EventCounters(), StageTimer()
    with start_server_thread(
        LinearModel(obs_dim=4, slots=2, seed=0),
        counters=counters, timer=timer,
    ) as h:
        c = ServeClient(h.address)
        c.reset()
        for _ in range(3):
            c.step(np.zeros(4, np.float32))
        # remote registration: the hub pulls the telemetry RPC per
        # scrape (how a separate scraper process would see the server)
        hub = TelemetryHub()
        c.register_with_hub(hub, "serve")
        snap = hub.scrape()
        assert snap["counters"]["serve_batches"] >= 1
        assert snap["stages"]["compute"]["count"] >= 1
        # histogram-backed percentiles, not zero-fills: the serve
        # stages carry real p50/p99 through the remote merge
        assert snap["stages"]["compute"]["p99_ms"] > 0.0
        assert (snap["stages"]["compute"]["p99_ms"]
                >= snap["stages"]["compute"]["p50_ms"])
        assert "serve" in snap["components"]
        # every serve counter is present even when zero
        for name in SERVE_EVENTS:
            assert name in snap["counters"], name
        c.close()


def test_trace_spans_ride_the_correlation_id():
    from blendjax.obs.spans import SpanRecorder, span_trace
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    rec = SpanRecorder()
    with start_server_thread(LinearModel(obs_dim=4, slots=2)) as h:
        c = ServeClient(h.address, span_recorder=rec)
        c.reset()
        c.step(np.zeros(4, np.float32))
        c.close()
    spans = rec.drain()
    names = {s["name"] for s in spans}
    assert "serve:step" in names and "serve_rpc:step" in names
    # server- and client-side spans of one RPC share the trace id
    srv = [s for s in spans if s["name"] == "serve:step"]
    cli = [s for s in spans if s["name"] == "serve_rpc:step"]
    assert span_trace(srv[0]) == span_trace(cli[0]) is not None


def test_the_cli_refuses_the_serial_flag(capsys):
    """The REP one-request-per-exchange mode is gone: its flag is an
    error, not a silently batched server."""
    from blendjax.serve import server

    with pytest.raises(SystemExit) as exc:
        server.main(["--address", "tcp://127.0.0.1:*", "--serial"])
    assert exc.value.code == 2
    assert "--serial" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# bench schema lock (satellite)
# ---------------------------------------------------------------------------


def test_bench_headline_carries_serve_metrics():
    import bench

    sb = {
        "phase": "serve_bench", "model": "seqformer", "clients": 8,
        "serve_qps": 2650.0, "serve_p50_ms": 2.4, "serve_p99_ms": 6.4,
        "serve_int8_x": 0.98,
        "serve_qps_modes": {"batched": 2650.0, "int8": 2600.0},
        "stages": {},
    }
    out = bench.assemble({"host_stream": {"items_per_sec": 1.0}},
                         serve_bench=sb)
    assert out["serve_bench"]["serve_qps"] == 2650.0
    line = bench.headline(out)
    assert line["serve_qps"] == 2650.0
    assert line["serve_p99_ms"] == 6.4
    assert line["serve_int8_x"] == 0.98
    assert len(json.dumps(line)) + 1 <= bench.HEADLINE_BYTE_BUDGET


def test_serve_bench_emits_locked_schema():
    from benchmarks._common import SERVE_BENCH_KEYS
    from benchmarks.serve_benchmark import measure

    rec = measure(seconds=1.2, clients=4, model="linear", rounds=1)
    assert all(k in rec for k in SERVE_BENCH_KEYS), [
        k for k in SERVE_BENCH_KEYS if k not in rec
    ]
    assert rec["serve_qps"] > 0
    assert rec["serve_p99_ms"] >= rec["serve_p50_ms"]
    for stage in SERVE_STAGES:
        assert stage in rec["stages"], stage
