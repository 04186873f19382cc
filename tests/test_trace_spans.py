"""The program's spans on the profiler's clock, and the stable names a
trace is read by (PERF.md section 3 holds the table of them).

Host side: ``StageTimer.stage`` / ``timing.span`` open a
``jax.profiler.TraceAnnotation`` where jax is loaded and import nothing
where it is not; ``PolicyServer``'s loop and ``SeqFormerModel``'s calls
leave the ``serve.*`` spans and the two phase counters.  Device side:
the Pallas kernels' ``name=``, the jitted steps' names and the
``named_scope``s — metadata only, so the arithmetic is bit-equal to the
same program traced without them."""

import contextlib
import functools
import glob
import os
import subprocess
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blendjax.models import seqformer
from blendjax.obs.hub import TelemetryHub
from blendjax.serve import LinearModel, ServeClient, start_server_thread
from blendjax.serve.server import SeqFormerModel
from blendjax.utils.timing import (
    SERVE_EVENTS,
    EventCounters,
    StageTimer,
    span,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: every span name of the server's loop and the model's calls: the
#: contract docs/serving.md and PERF.md tabulate
SERVER_SPANS = (
    "serve.idle", "serve.admit", "serve.prefill", "serve.window",
    "serve.tick", "serve.tick.assemble", "serve.tick.compute",
    "serve.retire", "serve.tick.reply", "serve.weights",
    "serve.poll", "serve.slice",
)
#: the phase clock's leaf phases as spans (``serve_fetch_wait_us`` is the
#: model's ``*.fence``): on the server's thread no two of them overlap
LEAF_SPANS = (
    "serve.idle", "serve.poll", "serve.slice", "serve.prefill",
    "serve.tick.assemble", "serve.tick.compute", "serve.step.fence",
    "serve.prefill.fence", "serve.tick.reply",
)
#: the two phases that may hold others, and which: admission a prefill's
#: dispatch, a weight poll the retires of what a staged snapshot finds
#: launched
HOLDERS = {
    "serve.admit": ("serve.prefill",),
    "serve.weights": ("serve.step.fence", "serve.prefill.fence",
                      "serve.tick.reply"),
}
MODEL_SPANS = (
    "serve.step.dispatch", "serve.step.fence",
    "serve.prefill.dispatch", "serve.prefill.fence", "serve.reset_rows",
)
TINY = dict(obs_dim=4, d_model=32, n_heads=2, n_layers=2, max_len=32)


def _host_events(trace_dir):
    """{name: [(start_ns, end_ns, stats)]} of the trace's host planes."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert files, f"no trace written under {trace_dir}"
    out = {}
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)))
    return out


@contextlib.contextmanager
def _profiled(trace_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the annotations alone: a small file
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class _OneSnapshot:
    """A WeightBus subscription that yields one snapshot, then nothing."""

    model = None

    def __init__(self, tree):
        self._snap = types.SimpleNamespace(
            model=None, version=1, step=1, tree=lambda: tree)

    def poll(self):
        snap, self._snap = self._snap, None
        return snap

    def close(self):
        pass


class _GatedReply:
    """A step's reply, ready when the test opens its gate."""

    def __init__(self, rows):
        self.rows, self.gate = rows, threading.Event()

    def is_ready(self):
        return self.gate.is_set()

    def __array__(self, dtype=None, copy=None):
        assert self.gate.wait(20.0), "the test never opened the gate"
        return self.rows


class _GatedModel:
    """``pred = sum(obs)``, handed out gated."""

    kind = "gated"
    obs_dim = 4

    def __init__(self):
        self.slots = self.pad_slot = 2
        self.calls = []

    def reset_rows(self, idx):
        pass

    def step_rows(self, idx, obs):
        self.calls.append(_GatedReply(obs.sum(-1, keepdims=True)))
        return self.calls[-1]


def _gated_session():
    """Two clients of a gated model: a's tick launched and not ready, so
    the window polls the wire in slices (``serve.poll``); b's step arrives
    in one and the slice is slept out (``serve.slice``); b's tick is
    launched behind a's, whose fetch then waits for the gate."""
    model, counters = _GatedModel(), EventCounters()
    with start_server_thread(model, counters=counters) as h:
        a = ServeClient(h.address, timeoutms=20000)
        b = ServeClient(h.address, timeoutms=20000)
        a.reset()
        b.reset()
        out = {}
        ta = threading.Thread(target=lambda: out.update(a=a.step(
            np.ones(4, np.float32))))
        ta.start()
        deadline = time.monotonic() + 20
        while not model.calls:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        time.sleep(0.02)  # the window polls
        tb = threading.Thread(target=lambda: out.update(b=b.step(
            np.ones(4, np.float32))))
        tb.start()
        while len(model.calls) < 2:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        time.sleep(0.02)  # a's fetch waits
        for reply in model.calls:
            reply.gate.set()
        ta.join(20)
        tb.join(20)
        assert out["a"]["pred"][0] == out["b"]["pred"][0] == 4.0
        after = a.stats()["counters"]
        a.close()
        b.close()
    return after


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced session: a ``LinearModel`` server answering a
    ``reset(prefix=)``, a few ``step``s and a weight swap, then a tiny
    ``SeqFormerModel`` driven the same way, then two clients of a model
    whose replies the test holds back."""
    trace_dir = tmp_path_factory.mktemp("trace")
    counters = EventCounters()
    t_wall = time.perf_counter()
    with _profiled(trace_dir):
        linear = LinearModel(obs_dim=4, slots=4)
        with start_server_thread(linear, counters=counters,
                                 timer=StageTimer()) as h:
            c = ServeClient(h.address, timeoutms=20000)
            before = c.stats()["counters"]
            c.reset(prefix=np.ones((5, 4), np.float32))
            for _ in range(3):
                c.step(np.ones(4, np.float32))
            h.server.subscriber = _OneSnapshot({"w": linear.w + 1.0})
            c.step(np.ones(4, np.float32))
            time.sleep(0.05)  # an empty poll or two
            after = c.stats()["counters"]
            c.close_episode()
            c.close()
        with start_server_thread(_tiny_served_model(), max_batch=2) as h:
            c = ServeClient(h.address, timeoutms=60000)
            c.reset(prefix=np.ones((3, 4), np.float32))
            c.step(np.ones(4, np.float32))
            c.close_episode()
            c.close()
        gated = _gated_session()
    wall_us = (time.perf_counter() - t_wall) * 1e6
    return types.SimpleNamespace(
        events=_host_events(str(trace_dir)), before=before, after=after,
        gated=gated, wall_us=wall_us)


@pytest.mark.parametrize("name", SERVER_SPANS + MODEL_SPANS)
def test_server_span_is_in_the_profilers_trace(served, name):
    assert served.events.get(name), sorted(
        n for n in served.events if n.startswith("serve"))


def _inside(served, inner, outer):
    """Every ``inner`` span lies within some ``outer`` span."""
    outers = [(lo, hi) for lo, hi, _ in served.events[outer]]
    return all(any(o_lo <= lo and hi <= o_hi for o_lo, o_hi in outers)
               for lo, hi, _ in served.events[inner])


def test_tick_phases_lie_inside_a_tick(served):
    # a tick has two halves: the launch (``serve.tick``: assemble and
    # the model call, which for a model on the device is the dispatch
    # alone) and the retire (the fetch, then the replies)
    for phase in ("assemble", "compute"):
        assert _inside(served, f"serve.tick.{phase}", "serve.tick"), phase
    assert _inside(served, "serve.tick.reply", "serve.retire")
    # and the model's own two halves: the dispatch inside the launch's
    # compute, the fence where the fetch happens
    assert _inside(served, "serve.step.dispatch", "serve.tick.compute")
    assert _inside(served, "serve.step.fence", "serve.retire")
    # every launched tick was retired, in order (a retire with ``rows``
    # is a tick's; one with ``prefill`` is a launched prefill's)
    launches = sorted(lo for lo, _, _ in served.events["serve.tick.compute"])
    retires = sorted(lo for lo, _, st in served.events["serve.retire"]
                     if "rows" in st)
    assert len(launches) == len(retires)
    assert all(a < b for a, b in zip(launches, retires))


def test_a_launched_prefill_is_dispatched_at_admission_and_fenced_at_retire(
        served):
    # the SeqFormer's reset(prefix=) of 3: ``serve.prefill`` spans the
    # dispatch, inside admission; the fence opens where the reply is
    # fetched, inside the retire that answers the reset
    assert _inside(served, "serve.prefill.dispatch", "serve.prefill")
    assert _inside(served, "serve.prefill", "serve.admit")
    assert _inside(served, "serve.prefill.fence", "serve.retire")
    prefills = [(lo, hi) for lo, hi, st in served.events["serve.prefill"]
                if st.get("len") == 3]
    fences = served.events["serve.prefill.fence"]
    retired = [(lo, hi) for lo, hi, st in served.events["serve.retire"]
               if st.get("prefill") == 3]
    assert len(prefills) == len(fences) == len(retired) == 1
    assert prefills[0][1] <= retired[0][0] <= fences[0][0]
    # a model that computes on the host launches nothing: the linear
    # server's prefill of 5 has no retire of its own
    assert not [st for _, _, st in served.events["serve.retire"]
                if st.get("prefill") == 5]


def test_span_arguments_become_event_stats(served):
    tick_stats = [st for _, _, st in served.events["serve.tick"] if st]
    assert {"rows": 1, "bucket": 1} in tick_stats
    assert {st.get("len") for _, _, st in
            served.events["serve.prefill"]} == {5, 3}


def test_prefill_and_idle_counters(served):
    for name in ("serve_prefill_us", "serve_idle_us",
                 "serve_ticks_overlapped", "serve_fetch_wait_us",
                 "serve_prefills_overlapped"):
        assert name in SERVE_EVENTS
        # the hub zero-fills them before any server has reported
        assert TelemetryHub().scrape()["counters"][name] == 0
    # one client: no tick was ever launched behind another, and the
    # program says so (the key is there, at 0)
    assert served.after["serve_ticks_overlapped"] == 0
    assert served.after["serve_prefills_overlapped"] == 0
    assert served.before.get("serve_prefill_us", 0) == 0
    assert served.after["serve_prefills"] == 1
    assert 0 < served.after["serve_prefill_us"] <= served.wall_us
    assert (0 < served.after["serve_idle_us"] - served.before.get(
        "serve_idle_us", 0) <= served.wall_us)
    # the counter and the span are one interval, read twice
    span_us = sum(hi - lo for lo, hi, st in served.events["serve.prefill"]
                  if st.get("len") == 5) / 1e3
    assert served.after["serve_prefill_us"] >= 0.5 * span_us


def test_the_window_polls_and_slices_are_spans(served):
    # a's tick in flight: the window read the wire in slices of a
    # millisecond, and b's step arrived in one, which was slept out
    polls = served.events["serve.poll"]
    assert len(polls) >= 5
    assert _inside(served, "serve.poll", "serve.window")
    assert _inside(served, "serve.slice", "serve.window")
    assert all(hi - lo < 50e6 for lo, hi, _ in served.events["serve.slice"])
    # the clock counted the same phases, and the wait at a's fetch
    assert served.gated["serve_poll_us"] > 0
    assert served.gated["serve_slice_us"] > 0
    assert served.gated["serve_fetch_wait_us"] >= 10_000


def test_the_leaf_spans_do_not_overlap(served):
    leaves = sorted((lo, hi, name) for name in LEAF_SPANS
                    for lo, hi, _ in served.events.get(name, ()))
    assert len(leaves) > 30
    for (lo, hi, name), (lo2, hi2, name2) in zip(leaves, leaves[1:]):
        assert hi <= lo2, (name, name2, hi - lo2)
    # admission and a weight poll hold no leaf but their own, whole
    for holder, held in HOLDERS.items():
        for lo, hi, _ in served.events[holder]:
            inside = [(a, b, n) for a, b, n in leaves if a < hi and lo < b]
            assert all(n in held and lo <= a and b <= hi
                       for a, b, n in inside), (holder, inside)


def test_stage_and_span_annotate_when_jax_is_loaded(tmp_path):
    timer = StageTimer()
    with _profiled(tmp_path):
        with timer.stage("device_put"):
            with span("free.span", rows=3) as s:
                s.set_metadata(bucket=4)
    events = _host_events(str(tmp_path))
    assert len(events["device_put"]) == 1
    (lo, hi, stats), = events["free.span"]
    assert stats == {"rows": 3, "bucket": 4}
    d_lo, d_hi, _ = events["device_put"][0]
    assert d_lo <= lo and hi <= d_hi
    assert timer.count("device_put") == 1  # still the program's stage


def test_stage_imports_nothing_where_jax_is_absent():
    code = (
        "import sys\n"
        "class Block:\n"
        "    def find_spec(self, name, path=None, target=None):\n"
        "        if name == 'jax' or name.startswith('jax.'):\n"
        "            raise ImportError('jax is blocked here')\n"
        "sys.meta_path.insert(0, Block())\n"
        "from blendjax.utils.timing import StageTimer, span\n"
        "t = StageTimer()\n"
        "with t.stage('recv'):\n"
        "    with span('free', rows=1) as s:\n"
        "        s.set_metadata(bucket=2)\n"
        "assert t.count('recv') == 1\n"
        "assert not [m for m in sys.modules if m.split('.')[0] == 'jax']\n"
        "print('ok')\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


# -- device-side names ------------------------------------------------------


def _pallas_names(jaxpr):
    """The ``name`` of every ``pallas_call`` in a (closed) jaxpr."""
    names = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue  # the kernel body holds no further call
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return names


def _flash_loss(q, k, v):
    from blendjax.ops.flash_attention import flash_attention

    out = flash_attention(q, k, v, True, None, 32, 32, True)
    return (out.astype(jnp.float32) ** 2).sum()


def _qkv(dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    return [jax.random.normal(k, (2, 64, 2, 32), dtype) for k in ks]


def _decode_frames(x):
    from blendjax.ops.image import decode_frames_pallas

    return decode_frames_pallas(x, interpret=True)


def _ssd_update(*args):
    from blendjax.ops.gdn_update import gdn_update

    return gdn_update(*args, delta=False)


def _ssd_update_args():
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    return [jnp.zeros((3, 2, 4, 8)), jnp.asarray([0, 2]),
            jax.random.normal(ks[0], (2, 8)), jax.random.normal(ks[1], (2, 8)),
            jax.random.normal(ks[2], (2, 2, 4)), jnp.ones((2, 2)),
            jax.random.uniform(ks[3], (2, 2))]


@pytest.mark.parametrize("name,fn,args", [
    ("flash_fwd", _flash_loss, _qkv),
    ("flash_bwd_dq", jax.grad(_flash_loss, argnums=(0, 1, 2)), _qkv),
    ("flash_bwd_dkv", jax.grad(_flash_loss, argnums=(0, 1, 2)), _qkv),
    ("decode_frames", _decode_frames,
     lambda: [jnp.zeros((2, 8, 8, 3), jnp.uint8)]),
    ("ssd_update", _ssd_update, _ssd_update_args),
])
def test_pallas_calls_carry_their_names(name, fn, args):
    assert name in _pallas_names(jax.make_jaxpr(fn)(*args()))


def _train_step():
    import optax

    from blendjax.models.train import TrainState, make_train_step

    params = seqformer.init(jax.random.PRNGKey(0), **TINY)
    opt = optax.adam(1e-3)
    step = make_train_step(functools.partial(
        seqformer.episode_loss_fn, compute_dtype=jnp.float32), opt,
        donate=False)
    state = TrainState.create(params, opt)
    return step.lower(state, {"episode": jnp.ones((2, 9, 4))})


def _tiny_served_model():
    return SeqFormerModel(
        seqformer.init(jax.random.PRNGKey(0), **TINY), slots=2, length=16)


def _serve_step():
    model = _tiny_served_model()
    return model._step.lower(
        model.params, model._cache, jnp.zeros(2, jnp.int32),
        jnp.ones((2, 4)))


def _serve_prefill():
    model = _tiny_served_model()
    return model._prefill.lower(
        model.params, model._cache, jnp.zeros(1, jnp.int32),
        jnp.ones((3, 4)))


TINY_TOKENS = dict(
    hidden_size=32, num_attention_heads=4, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=10000,
    rope_scaling=None, num_hidden_layers=2, first_k_dense_replace=1,
    intermediate_size=64, moe_intermediate_size=16, num_experts=8,
    num_experts_per_tok=2, routed_scaling_factor=2.5, num_shared_experts=1,
    vocab_size=64)


def _tiny_token_model():
    return SeqFormerModel(
        seqformer.init_token_model(jax.random.PRNGKey(0), TINY_TOKENS,
                                   held=(2, 4)), slots=2, length=16)


def _token_step():
    model = _tiny_token_model()
    return model._step.lower(
        model.params, model._cache, jnp.zeros(2, jnp.int32),
        jnp.ones((2, 1), jnp.int32))


def _token_prefill():
    model = _tiny_token_model()
    return model._prefill.lower(
        model.params, model._cache, jnp.zeros(1, jnp.int32),
        jnp.ones((3, 1), jnp.int32))


TINY_HYBRID = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=64, num_hidden_layers=8, mb_per_layer=2,
    sliding_window=4, layer_norm_eps=1e-5, vocab_size=64, mamba_d_state=4,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=2)


def _tiny_hybrid_model():
    return SeqFormerModel(
        seqformer.init_hybrid_model(jax.random.PRNGKey(0), TINY_HYBRID),
        slots=2, length=16)


def _hybrid_step():
    model = _tiny_hybrid_model()
    return model._step.lower(
        model.params, model._cache, jnp.zeros(2, jnp.int32),
        jnp.ones((2, 1), jnp.int32))


def _hybrid_prefill():
    model = _tiny_hybrid_model()
    return model._prefill.lower(
        model.params, model._cache, jnp.zeros(1, jnp.int32),
        jnp.ones((3, 1), jnp.int32))


TINY_LINEAR = dict(
    hidden_size=32, intermediate_size=64, num_hidden_layers=4,
    num_attention_heads=2, num_key_value_heads=2,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rms_norm_eps=1e-6, vocab_size=64,
    tie_word_embeddings=False)


def _tiny_linear_model():
    return SeqFormerModel(
        seqformer.init_linear_hybrid_model(jax.random.PRNGKey(0),
                                           TINY_LINEAR),
        slots=2, length=16)


def _linear_step():
    model = _tiny_linear_model()
    return model._step.lower(
        model.params, model._cache, jnp.zeros(2, jnp.int32),
        jnp.ones((2, 1), jnp.int32))


def _linear_prefill():
    model = _tiny_linear_model()
    return model._prefill.lower(
        model.params, model._cache, jnp.zeros(1, jnp.int32),
        jnp.ones((3, 1), jnp.int32))


TINY_WINDOW = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_hidden_layers=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    sliding_window=4, moe_intermediate_size=16, num_experts=8,
    num_experts_held=4, num_experts_per_tok=2, norm_topk_prob=True,
    rms_norm_eps=1e-6, vocab_size=64, rope_parameters={
        "full_attention": dict(
            rope_type="yarn", rope_theta=10000, factor=4, beta_fast=32,
            beta_slow=1, original_max_position_embeddings=64,
            attention_factor=1.2),
        "sliding_attention": dict(rope_type="default", rope_theta=1000)})


def _tiny_window_model():
    from chipbench import reference_mellum2

    return SeqFormerModel(
        seqformer.describe_token_model(
            reference_mellum2.make_params(TINY_WINDOW, 0, jnp.float32),
            TINY_WINDOW),
        slots=2, length=16)


def _window_step():
    model = _tiny_window_model()
    return model._step.lower(
        model.params, model._cache, jnp.zeros(2, jnp.int32),
        jnp.ones((2, 1), jnp.int32))


def _window_prefill():
    model = _tiny_window_model()
    return model._prefill.lower(
        model.params, model._cache, jnp.zeros(1, jnp.int32),
        jnp.ones((6, 1), jnp.int32))


TINY_SSD = dict(
    hidden_size=32, shared_intermediate_size=64, num_hidden_layers=2,
    num_attention_heads=2, num_key_value_heads=2,
    layer_types=["mamba", "attention"], mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_n_groups=1,
    mamba_chunk_size=4, rms_norm_eps=1e-5, vocab_size=64,
    tie_word_embeddings=True, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=8, attention_multiplier=0.0625,
    position_embedding_type="nope")


def _tiny_ssd_model():
    return SeqFormerModel(
        seqformer.init_ssd_hybrid_model(jax.random.PRNGKey(0), TINY_SSD),
        slots=2, length=16)


def _ssd_step():
    model = _tiny_ssd_model()
    return model._step.lower(
        model.params, model._cache, jnp.zeros(2, jnp.int32),
        jnp.ones((2, 1), jnp.int32))


def _ssd_prefill():
    model = _tiny_ssd_model()
    return model._prefill.lower(
        model.params, model._cache, jnp.zeros(1, jnp.int32),
        jnp.ones((6, 1), jnp.int32))


def test_a_routed_model_of_window_and_full_layers_counts_both_sets():
    """One step of a routed model of mixed kinds returns the held-share
    layers' counts and the hybrid step's beside its reply; nothing is
    recurrent, so a reset zeroes nothing and no state bytes move."""
    from blendjax.serve.server import HYBRID_EVENTS, MOE_EVENTS

    model = _tiny_window_model()
    assert model._step_events == MOE_EVENTS + HYBRID_EVENTS[:3]
    model.reset_rows(np.asarray([1]))
    model.prefill_rows(np.asarray([1]), np.ones((5, 1), np.int32))
    for _ in range(3):  # positions 5, 6, 7; the pad row beside them
        np.asarray(model.step_rows(np.asarray([1, model.pad_slot]),
                                   np.ones((2, 1), np.int32)))
    events = model.drain_events()
    held = events.pop("serve_moe_assignments_held")
    hit = events.pop("serve_moe_experts_hit")
    assert 0 < hit <= held <= 3 * 4 * 2
    assert events == {
        "serve_moe_assignments": 3 * 4 * 2,
        "serve_ctx_positions": 6 + 7 + 8, "serve_rows_stepped": 3,
        "serve_window_positions": 3 * 4, "serve_state_bytes": 0}


@pytest.mark.parametrize("lower", [_window_step, _window_prefill])
def test_each_attention_kind_has_its_scope_under_attn(lower):
    text = lower().as_text(debug_info=True)
    assert "/attn/window/" in text and "/attn/full/" in text


def test_linear_attention_model_counters_move_in_a_served_episode():
    """A model without window layers counts no window positions, and the
    other counts stand; ``serve_state_bytes`` is twice the state and tails
    behind a row, a real row stepped."""
    from blendjax.serve.server import HYBRID_EVENTS

    assert "serve_state_bytes" in HYBRID_EVENTS
    assert "serve_state_bytes" in SERVE_EVENTS
    assert TelemetryHub().scrape()["counters"]["serve_state_bytes"] == 0
    model = _tiny_linear_model()
    row = 3 * (2 * 16 * 8 * 4 + 3 * (16 + 16 + 32) * 4)  # float32 tails here
    assert seqformer.state_row_bytes(model._cache) == row
    model.reset_rows(np.asarray([1]))
    model.prefill_rows(np.asarray([1]), np.ones((5, 1), np.int32))
    for _ in range(3):  # positions 5, 6, 7; the pad row beside them
        np.asarray(model.step_rows(np.asarray([1, model.pad_slot]),
                                   np.ones((2, 1), np.int32)))
    assert model.drain_events() == {
        "serve_ctx_positions": 6 + 7 + 8, "serve_rows_stepped": 3,
        "serve_window_positions": 0, "serve_state_resets": 1,
        "serve_state_bytes": 3 * 2 * row}
    assert model.drain_events() == {}


def test_mamba2_model_counters_move_in_a_served_episode():
    """A Mamba-2 row's state (64 x 16 x 16 float32 a layer here) and its
    tail are what ``serve_state_bytes`` counts twice a real row stepped;
    a reset zeroes both leaves of the row and counts once."""
    model = _tiny_ssd_model()
    row = 4 * 16 * 16 * 4 + 3 * (64 + 32) * 4  # float32 tail here
    assert seqformer.state_row_bytes(model._cache) == row
    model.reset_rows(np.asarray([1]))
    model.prefill_rows(np.asarray([1]), np.ones((5, 1), np.int32))
    for _ in range(3):  # positions 5, 6, 7; the pad row beside them
        np.asarray(model.step_rows(np.asarray([1, model.pad_slot]),
                                   np.ones((2, 1), np.int32)))
    assert model.drain_events() == {
        "serve_ctx_positions": 6 + 7 + 8, "serve_rows_stepped": 3,
        "serve_window_positions": 0, "serve_state_resets": 1,
        "serve_state_bytes": 3 * 2 * row}
    kept = {name: np.array(model._cache[name][0][1])
            for name in ("ssd_s", "ssd_tail")}
    assert all(np.any(leaf != 0) for leaf in kept.values())
    model.reset_rows(np.asarray([1]))
    for name in kept:
        assert not np.any(np.asarray(model._cache[name][0][1]))
    assert model.drain_events() == {"serve_state_resets": 1}


def test_hybrid_model_counters_move_in_a_served_episode():
    from blendjax.serve.server import HYBRID_EVENTS

    for name in HYBRID_EVENTS:
        assert name in SERVE_EVENTS
        assert TelemetryHub().scrape()["counters"][name] == 0
    model = _tiny_hybrid_model()
    model.reset_rows(np.asarray([1]))
    model.prefill_rows(np.asarray([1]), np.ones((5, 1), np.int32))
    for _ in range(3):  # positions 5, 6, 7; the pad row beside them
        np.asarray(model.step_rows(np.asarray([1, model.pad_slot]),
                                   np.ones((2, 1), np.int32)))
    assert model.drain_events() == {
        "serve_ctx_positions": 6 + 7 + 8, "serve_rows_stepped": 3,
        "serve_window_positions": 3 * 4, "serve_state_resets": 1,
        "serve_state_bytes": 3 * 2 * seqformer.state_row_bytes(model._cache)}
    assert model.drain_events() == {}


@pytest.mark.parametrize("name", [
    "serve_moe_assignments", "serve_moe_assignments_held",
    "serve_moe_experts_hit"])
def test_routed_model_counters_are_in_the_vocabulary(name):
    assert name in SERVE_EVENTS
    assert TelemetryHub().scrape()["counters"][name] == 0


@pytest.mark.parametrize("lower,module,scopes", [
    (_token_step, "serve_step",
     ("decode", "mla", "absorb", "scatter", "gather", "moe", "route",
      "experts", "shared", "mlp", "ln", "head")),
    (_token_prefill, "serve_prefill",
     ("forward", "mla", "expand", "scatter", "moe", "route", "experts",
      "shared", "mlp", "ln", "head")),
    (_hybrid_step, "serve_step",
     ("decode", "ssm", "conv", "update", "gmu", "attn", "diff", "window",
      "full", "cross", "scatter", "gather", "mlp", "ln", "head")),
    (_hybrid_prefill, "serve_prefill",
     ("forward", "ssm", "conv", "scan", "gmu", "attn", "diff", "window",
      "full", "cross", "scatter", "mlp", "ln", "head")),
    (_linear_step, "serve_step",
     ("decode", "gdn", "conv", "gate", "update", "attn", "full", "scatter",
      "gather", "mlp", "ln", "head")),
    (_linear_prefill, "serve_prefill",
     ("forward", "gdn", "conv", "gate", "chunk", "attn", "full", "scatter",
      "mlp", "ln", "head")),
    (_ssd_step, "serve_step",
     ("decode", "ssd", "conv", "update", "norm", "attn", "full", "scatter",
      "gather", "mlp", "ln", "head")),
    (_ssd_prefill, "serve_prefill",
     ("forward", "ssd", "conv", "chunk", "norm", "attn", "full", "scatter",
      "mlp", "ln", "head")),
    (_window_step, "serve_step",
     ("decode", "attn", "window", "full", "scatter", "gather", "moe",
      "route", "experts", "ln", "head")),
    (_window_prefill, "serve_prefill",
     ("forward", "attn", "window", "full", "scatter", "moe", "route",
      "experts", "ln", "head")),
    (_train_step, "train_step",
     ("loss", "optimizer", "attn", "mlp", "ln")),
    (_serve_step, "serve_step",
     ("gather", "decode", "scatter", "attn", "mlp", "ln")),
    (_serve_prefill, "serve_prefill",
     ("forward", "scatter", "attn", "mlp", "ln")),
])
def test_lowering_holds_the_step_and_scope_names(lower, module, scopes):
    text = lower().as_text(debug_info=True)
    assert f"jit_{module}" in text
    for scope in scopes:
        # a scope is one component of an operation's name stack
        assert f"/{scope}/" in text or f"({scope})" in text, scope


@pytest.mark.parametrize("lower,scopes", [
    (_ssd_step, ("ssd/conv", "ssd/update", "ssd/norm")),
    (_ssd_prefill, ("ssd/conv", "ssd/chunk", "ssd/norm")),
])
def test_the_mamba2_scopes_lie_under_ssd(lower, scopes):
    """What a trace of a Mamba-2 layer shows: its convolution, its state's
    update (decode) or chunks (prefill) and its gated norm, each under the
    layer's ``ssd`` scope."""
    text = lower().as_text(debug_info=True)
    for scope in scopes:
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope


# -- names change no arithmetic ---------------------------------------------


@contextlib.contextmanager
def _names_off(monkeypatch):
    """The parent's program: no ``named_scope``, no kernel ``name=``."""
    from jax.experimental import pallas as pl

    real_call = pl.pallas_call

    def unnamed_call(*args, name=None, **kwargs):
        return real_call(*args, **kwargs)

    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope",
                  lambda name: contextlib.nullcontext())
        m.setattr(pl, "pallas_call", unnamed_call)
        m.setattr(seqformer, "_ln_apply", seqformer._ln_apply.__wrapped__)
        yield


def _decode_two_steps(params, cache, obs):
    preds = []
    for t in range(obs.shape[0]):
        pred, cache = seqformer.decode_step(
            params, cache, obs[t], compute_dtype=jnp.float32)
        preds.append(pred)
    return jnp.stack(preds), cache["k"][0]


def _decode_case():
    params = seqformer.init(jax.random.PRNGKey(3), **TINY)
    cache = seqformer.init_cache(params, 3, dtype=jnp.float32, length=8,
                                 per_row=True)
    obs = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 4))
    return params, cache, obs


@pytest.mark.parametrize("fn,args", [
    (_flash_loss, _qkv),
    (jax.grad(_flash_loss, argnums=(0, 1, 2)), _qkv),
    (jax.grad(_flash_loss, argnums=(0, 1, 2)),
     functools.partial(_qkv, jnp.bfloat16)),
    (_decode_two_steps, _decode_case),
], ids=["flash_fwd", "flash_bwd", "flash_bwd_bf16", "decode_step"])
def test_names_change_no_arithmetic(monkeypatch, fn, args):
    # a fresh wrapper each time: nothing traced with names is reused
    # for the run without them
    named = jax.jit(lambda *a: fn(*a))(*args())
    named_text = jax.jit(lambda *a: fn(*a)).lower(*args()).as_text(
        debug_info=True)
    with _names_off(monkeypatch):
        bare = jax.jit(lambda *a: fn(*a))(*args())
        bare_text = jax.jit(lambda *a: fn(*a)).lower(*args()).as_text(
            debug_info=True)
    assert named_text != bare_text  # the names were there, and went
    for a, b in zip(jax.tree.leaves(named), jax.tree.leaves(bare)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
