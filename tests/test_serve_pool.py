"""The slot pool served in place (docs/serving.md "KV-cache slot pool").

``SeqFormerModel`` donates its pool to the jitted step and the jitted
prefill, which write only the positions that changed
(``decode_step(slots=idx)``: one K/V position per layer and row) and
hand the same buffers back.  Locked here, on the CPU: the donation
takes and no pool-sized temporary is compiled; rows outside a call are
bit-identical after it; padding changes no real row's answer; the pooled
step equals the old gather -> ``decode_step`` -> scatter arithmetic
across the parity matrix; and a call that is refused, or that fails
after the pool was donated, costs no client a wrong answer.
"""

import numpy as np
import pytest

from blendjax.btt.faults import FaultPolicy
from blendjax.utils.timing import EventCounters

OBS = 5


def _params(seed=0, **kwargs):
    import jax

    from blendjax.models import seqformer

    return seqformer.init(
        jax.random.PRNGKey(seed), obs_dim=OBS, d_model=32, n_heads=4,
        n_layers=2, max_len=32, **kwargs,
    )


def _model(slots=4, length=16, **kwargs):
    from blendjax.serve.server import SeqFormerModel

    return SeqFormerModel(_params(), slots=slots, length=length, **kwargs)


def _pool(model):
    """The pool as host arrays (a copy: the device buffers are the
    model's alone)."""
    import jax

    return jax.tree.map(np.array, model._cache)


def _warm(model, rng, rows=(0, 1, 2)):
    """Three live episodes at different positions: a prefill into the
    first row, steps on all of them."""
    rows = np.asarray(rows)
    model.prefill_rows(rows[:1], rng.standard_normal((3, OBS), np.float32))
    for n in (len(rows), len(rows) - 1):
        model.step_rows(rows[:n], rng.standard_normal((n, OBS), np.float32))


# -- the donation takes, and nothing pool-sized is compiled ------------------


def _call_step(model, rng):
    model.step_rows(np.asarray([0, 2]),
                    rng.standard_normal((2, OBS), np.float32))


def _call_prefill(model, rng):
    model.prefill_rows(np.asarray([1]),
                       rng.standard_normal((6, OBS), np.float32))


@pytest.mark.parametrize("call", [_call_step, _call_prefill],
                         ids=["step", "prefill"])
def test_a_call_deletes_the_pool_it_was_given(call):
    import jax

    rng = np.random.default_rng(0)
    model = _model()
    _warm(model, rng)
    before = jax.tree.leaves(model._cache)
    call(model, rng)
    assert all(leaf.is_deleted() for leaf in before)
    assert not any(
        leaf.is_deleted() for leaf in jax.tree.leaves(model._cache))


def _lower_step(model):
    import jax.numpy as jnp

    return model._step.lower(
        model.params, model._cache, jnp.zeros(2, jnp.int32),
        jnp.ones((2, OBS)))


def _lower_prefill(model):
    import jax.numpy as jnp

    return model._prefill.lower(
        model.params, model._cache, jnp.zeros(1, jnp.int32),
        jnp.ones((6, OBS)))


@pytest.mark.parametrize("lower", [_lower_step, _lower_prefill],
                         ids=["step", "prefill"])
def test_compiled_call_aliases_the_pool_and_copies_none_of_it(lower):
    # a pool tensor far larger than a bucket's rows and activations, so
    # a copy of one could not hide among the temporaries
    model = _model(slots=63, length=32)
    tensor = model._cache["k"][0]
    compiled = lower(model).compile()
    mem = compiled.memory_analysis()
    n_tensors = 2 * len(model._cache["k"])
    assert mem.alias_size_in_bytes >= n_tensors * tensor.nbytes
    assert mem.temp_size_in_bytes < 2 * tensor.nbytes
    shape = "f32[%s]" % ",".join(map(str, tensor.shape))
    copies = [line for line in compiled.as_text().splitlines()
              if " copy(" in line and shape in line]
    assert not copies, copies[:2]


# -- what a call leaves alone -------------------------------------------------


def test_rows_outside_a_step_are_bit_identical_and_one_position_moves():
    rng = np.random.default_rng(1)
    model = _model()
    _warm(model, rng)
    before = _pool(model)
    idx = np.asarray([0, 2])
    model.step_rows(idx, rng.standard_normal((2, OBS), np.float32))
    after = _pool(model)
    others = np.setdiff1d(np.arange(model.slots + 1), idx)
    np.testing.assert_array_equal(after["pos"][others], before["pos"][others])
    np.testing.assert_array_equal(after["pos"][idx], before["pos"][idx] + 1)
    for name in ("k", "v"):
        for a, b in zip(after[name], before[name]):
            np.testing.assert_array_equal(a[others], b[others])
            for row in idx:
                moved = np.flatnonzero(
                    (a[row] != b[row]).any(axis=(1, 2)))
                assert moved.tolist() == [before["pos"][row] % model.length]


def test_rows_outside_a_prefill_are_bit_identical():
    rng = np.random.default_rng(2)
    model = _model()
    _warm(model, rng)
    before = _pool(model)
    model.prefill_rows(np.asarray([3]),
                       rng.standard_normal((6, OBS), np.float32))
    after = _pool(model)
    others = np.setdiff1d(np.arange(model.slots + 1), [3])
    np.testing.assert_array_equal(after["pos"][others], before["pos"][others])
    assert after["pos"][3] == 6
    for name in ("k", "v"):
        for a, b in zip(after[name], before[name]):
            np.testing.assert_array_equal(a[others], b[others])
            np.testing.assert_array_equal(a[3, 6:], b[3, 6:])
            assert (a[3, :6] != b[3, :6]).any(axis=(1, 2)).all()


def test_padding_with_the_pad_row_changes_no_real_rows_answer():
    rng = np.random.default_rng(3)
    plain, padded = _model(), _model()
    for model in (plain, padded):
        _warm(model, np.random.default_rng(30))
    pad = padded.pad_slot
    for _ in range(3):
        obs = rng.standard_normal((2, OBS), np.float32)
        want = plain.step_rows(np.asarray([2, 0]), obs)
        got = padded.step_rows(
            np.asarray([2, 0, pad, pad]),
            np.concatenate([obs, np.zeros((2, OBS), np.float32)]))
        np.testing.assert_allclose(got[:2], want, atol=1e-6, rtol=1e-6)
    for name in ("k", "v"):
        for a, b in zip(_pool(padded)[name], _pool(plain)[name]):
            np.testing.assert_allclose(a[:pad], b[:pad], atol=1e-6)


# -- the pooled step against the old arithmetic ------------------------------


def _old_step(params, cache, idx, obs, *, window):
    """What the server ran before the pool was served in place: gather
    the rows, ``decode_step`` on the private copy, scatter whole rows
    back."""
    import jax.numpy as jnp

    from blendjax.models import seqformer

    rows = {
        "pos": cache["pos"][idx],
        "k": [k[idx] for k in cache["k"]],
        "v": [v[idx] for v in cache["v"]],
    }
    pred, new = seqformer.decode_step(
        params, rows, obs, compute_dtype=jnp.float32, window=window)
    cache = {
        "pos": cache["pos"].at[idx].set(new["pos"]),
        "k": [c.at[idx].set(n) for c, n in zip(cache["k"], new["k"])],
        "v": [c.at[idx].set(n) for c, n in zip(cache["v"], new["v"])],
    }
    return pred, cache


@pytest.mark.parametrize(
    "init,window,served",
    [
        (dict(), None, dict()),
        (dict(), 4, dict()),
        (dict(pos_encoding="rope"), None, dict()),
        (dict(pos_encoding="rope"), 4, dict()),
        (dict(n_kv_heads=2), None, dict()),
        (dict(), 4, dict(cache_dtype="bfloat16")),
        (dict(), 4, dict(int8=True)),
    ],
    ids=["learned", "learned-windowed", "rope", "rope-windowed", "gqa",
         "bf16-cache", "int8"],
)
def test_pooled_step_matches_gather_decode_scatter(init, window, served):
    """Rows at heterogeneous positions, a different subset every tick,
    twelve ticks: a windowed ring of 4 wraps three times."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel

    served = dict(served)
    if "cache_dtype" in served:
        served["cache_dtype"] = jnp.dtype(served["cache_dtype"])
    length = 16 if window is None else window
    model = SeqFormerModel(_params(**init), slots=4, length=length,
                           window=window, compute_dtype=jnp.float32,
                           **served)
    cache = seqformer.init_cache(
        model.params, model.slots + 1, length=length, per_row=True,
        dtype=served.get("cache_dtype", jnp.float32))
    old = jax.jit(_old_step, static_argnames="window")
    rng = np.random.default_rng(4)
    for tick in range(12):
        idx = np.sort(rng.choice(4, size=1 + tick % 4, replace=False))
        obs = rng.standard_normal((len(idx), OBS), np.float32)
        want, cache = old(model.params, cache, jnp.asarray(idx),
                          jnp.asarray(obs), window=window)
        got = model.step_rows(idx, obs)
        np.testing.assert_allclose(got, np.asarray(want),
                                   atol=1e-6, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(model._cache), jax.tree.leaves(cache)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a[:4], np.float32), np.asarray(b[:4], np.float32),
            atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("shape,limit,pieces", [
    ((5, 16, 2, 4), 1 << 18, 1),   # a row fits: the plain gather
    ((5, 16, 2, 4), 32, 4),        # 4 positions of 8 elements a piece
    ((5, 12, 2, 4), 40, 3),        # the smallest divisor of 12 that fits
    ((5, 7, 2, 4), 8, 7),          # a prime ring: one position a piece
    ((5, 6, 2, 4), 4, 6),          # not even one position fits
], ids=["whole-row", "quarters", "thirds", "prime", "over"])
def test_pool_rows_in_pieces_are_the_plain_gather(monkeypatch, shape, limit,
                                                  pieces):
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer

    monkeypatch.setattr(seqformer, "_GATHER_SLICE_ELEMS", limit)
    pool = jax.random.normal(jax.random.PRNGKey(0), shape)
    slots = jnp.asarray([3, 0, 4, 4])
    # a fresh wrapper: the limit is read when the function is traced
    rows = jax.jit(lambda pool, slots: seqformer._pool_rows(pool, slots))
    text = rows.lower(pool, slots).as_text()
    piece = "tensor<%dx" % (len(slots) * pieces) + "x".join(
        map(str, (shape[1] // pieces,) + shape[2:]))
    assert piece in text, piece
    np.testing.assert_array_equal(
        np.asarray(rows(pool, slots)),
        np.asarray(pool)[np.asarray(slots)])


def test_slots_need_a_per_row_cache():
    import jax.numpy as jnp

    from blendjax.models import seqformer

    params = _params()
    cache = seqformer.init_cache(params, 2, dtype=jnp.float32, length=8)
    with pytest.raises(ValueError, match="per-row cache"):
        seqformer.decode_step(params, cache, jnp.ones((2, OBS)),
                              slots=jnp.arange(2))


# -- refusals leave the pool as it was ---------------------------------------


def _refuse_long_prefix(model):
    with pytest.raises(ValueError, match="exceeds the 16-slot"):
        model.prefill_rows(np.asarray([3]),
                           np.zeros((17, OBS), np.float32))


def _refuse_prefix_width(model):
    with pytest.raises(ValueError, match="prefix shape"):
        model.prefill_rows(np.asarray([3]),
                           np.zeros((4, OBS + 1), np.float32))


def _refuse_obs_shape(model):
    with pytest.raises(ValueError, match="obs shape"):
        model.step_rows(np.asarray([0, 1]),
                        np.zeros((2, OBS + 2), np.float32))


def _refuse_first_compile(model):
    # a bucket never compiled whose idx cannot be traced: the failure
    # comes out of the jitted call itself, before it runs
    with pytest.raises(TypeError):
        model.step_rows(np.asarray([0.5, 1.5, 2.5]),
                        np.zeros((3, OBS), np.float32))


@pytest.mark.parametrize("refuse", [
    _refuse_long_prefix, _refuse_prefix_width, _refuse_obs_shape,
    _refuse_first_compile,
], ids=["prefix-length", "prefix-width", "obs-shape", "first-compile"])
def test_a_refused_call_leaves_the_pool_and_live_episodes_as_they_were(
        refuse):
    import jax

    refused, twin = _model(), _model()
    for model in (refused, twin):
        _warm(model, np.random.default_rng(50))
    leaves = jax.tree.leaves(refused._cache)
    refuse(refused)
    assert refused.pool_rebuilds == 0
    assert all(a is b for a, b in
               zip(jax.tree.leaves(refused._cache), leaves))
    assert not any(leaf.is_deleted() for leaf in leaves)
    rng = np.random.default_rng(5)
    for _ in range(2):
        obs = rng.standard_normal((3, OBS), np.float32)
        np.testing.assert_array_equal(
            refused.step_rows(np.arange(3), obs),
            twin.step_rows(np.arange(3), obs))


def test_wrong_obs_shape_over_the_wire_costs_that_request_only():
    from blendjax.serve import ServeClient, start_server_thread

    counters = EventCounters()
    rng = np.random.default_rng(6)
    ep = rng.standard_normal((4, OBS), np.float32)
    twin = _model()
    twin.reset_rows(np.asarray([0]))
    with start_server_thread(_model(), counters=counters) as h:
        c = ServeClient(h.address, timeoutms=20000,
                        fault_policy=FaultPolicy(max_retries=0))
        c.reset()
        preds = [c.step(ep[0])["pred"]]
        with pytest.raises(RuntimeError, match="obs shape"):
            c.rpc("step", {"slot": c.slot, "episode": c.episode,
                           "obs": np.zeros(OBS + 2, np.float32)},
                  raw_buffers=True)
        preds += [c.step(ep[t])["pred"] for t in range(1, 4)]
        c.close()
    want = [twin.step_rows(np.asarray([0]), ep[t][None])[0]
            for t in range(4)]
    np.testing.assert_allclose(np.stack(preds), np.stack(want),
                               atol=1e-6, rtol=1e-6)
    counts = counters.snapshot()
    assert counts["serve_errors"] == 1
    assert counts.get("serve_pool_rebuilds", 0) == 0


# -- a donated call that fails costs the pool, never a wrong answer ----------


def _fail_after_dispatch(model, name):
    """Make the jitted ``name`` raise AFTER it has run: the pool it was
    given is gone, as after a device failure past dispatch."""
    real = getattr(model, name)

    def failing(*args):
        real(*args)
        raise RuntimeError("device fault (injected)")

    setattr(model, name, failing)
    return lambda: setattr(model, name, real)


@pytest.mark.parametrize("name,call", [
    ("_step", _call_step), ("_prefill", _call_prefill),
], ids=["step", "prefill"])
def test_a_failed_donated_call_rebuilds_an_empty_pool(name, call):
    import jax

    from blendjax.serve.server import SlotPoolLost

    rng = np.random.default_rng(7)
    model = _model()
    _warm(model, rng)
    restore = _fail_after_dispatch(model, name)
    with pytest.raises(SlotPoolLost, match="device fault"):
        call(model, rng)
    restore()
    assert model.pool_rebuilds == 1
    fresh = _pool(_model())
    for a, b in zip(jax.tree.leaves(_pool(model)), jax.tree.leaves(fresh)):
        np.testing.assert_array_equal(a, b)
    call(model, rng)  # and it serves again
    assert model.pool_rebuilds == 1


@pytest.mark.parametrize("name", ["_step", "_prefill"])
def test_lost_pool_over_the_wire_drops_leases_and_never_answers(name):
    from blendjax.serve import ServeClient, start_server_thread

    counters = EventCounters()
    rng = np.random.default_rng(8)
    ep = rng.standard_normal((6, OBS), np.float32)
    model = _model()
    with start_server_thread(model, counters=counters) as h:
        policy = FaultPolicy(max_retries=0)
        victim = ServeClient(h.address, timeoutms=20000,
                             fault_policy=policy)
        other = ServeClient(h.address, timeoutms=20000,
                            fault_policy=policy)
        victim.reset(prefix=ep[:2])
        victim.step(ep[2])
        restore = _fail_after_dispatch(model, name)
        if name == "_step":
            with pytest.raises(RuntimeError, match="batched step failed"):
                victim.step(ep[3])
        else:
            with pytest.raises(RuntimeError, match="prefill failed"):
                other.reset(prefix=ep[:3])
        restore()
        assert counters.snapshot()["serve_pool_rebuilds"] == 1
        stats = other.stats()
        assert stats["live_slots"] == 0
        assert stats["free_slots"] == model.slots
        # the tenant of the lost pool gets the lease error, not a
        # prediction off an empty cache
        with pytest.raises(RuntimeError, match="unknown episode slot"):
            victim.step(ep[3])
        # ... and resumes by reset(), answered as a fresh episode is
        reply = victim.reset(prefix=ep[:4])
        twin = _model()
        np.testing.assert_allclose(
            reply["pred"], twin.prefill_rows(np.asarray([0]), ep[:4]),
            atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(
            victim.step(ep[4])["pred"],
            twin.step_rows(np.asarray([0]), ep[4][None])[0],
            atol=1e-6, rtol=1e-6)
        assert counters.snapshot()["serve_pool_rebuilds"] == 1
        victim.close()
        other.close()
