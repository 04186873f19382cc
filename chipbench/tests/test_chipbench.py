"""CPU rehearsal of the on-chip benchmark, at tiny sizes.

The drivers are the ones ``chipbench/run.py`` calls on the chip, given tiny
configuration dicts: producers and the load generator are real children.  The
fault tests skip the harness's look for a chip and drive the rest of a run
with the timed path broken underneath; the control tests compute in the
precision below the configuration's.  Run with
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import flops, reference, run, trace_reduce  # noqa: E402

TINY = {"obs_dim": 4, "d_model": 32, "n_heads": 4, "head_dim": 8,
        "n_layers": 2, "d_ff": 64, "pos_encoding": "learned"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
FULL = {"obs_dim": 32, "d_model": 1024, "n_heads": 8, "n_layers": 8,
        "d_ff": 4096, "max_len": 512}
# the CPU runs float32 throughout, so the program sits at rounding from the
# reference and the limits can be far below the chip's
TRAIN_LIMITS = {"loss1_gap": 1e-4, "loss2_gap": 1e-4, "loss3_gap": 1e-4,
                "grad_norm_gap": 1e-3, "delta_norm_gap": 1e-3}
# (three bfloat16 passes read 2e-5 to 1e-4 here, float32 1e-6)
SERVE_LIMITS = {"pred_gap_max": 1e-5, "pred_gap_rms": 5e-6}


def _ctx(kind, seed=2**31 + 5, **over):
    if kind == "train":
        config = {
            "model": dict(TINY, max_len=16), "param_dtype": "float32",
            "compute_dtype": "float32", "control_quant": "int8",
            "attention": {"kernel": "flash", "causal": True,
                          "block_q": "auto", "block_kv": "auto"},
            "optimizer": {"name": "adam", "learning_rate": 1e-4, "b1": 0.9,
                          "b2": 0.999, "eps": 1e-8},
            "batch_size": 4, "seq_len": 16, "donate_state": True}
        workload = {
            "driver": "chipbench.drivers.train_stream:run",
            "check": {"reference_steps": 3, "reference_row_block": 2,
                      "limits": TRAIN_LIMITS},
            "traffic": {"producers": 2, "transport": "shm",
                        "raw_buffers": True, "episode_len": 17,
                        "amplitude": [0.5, 1.5], "stream_workers": 2,
                        "run_ahead_steps": 2}}
    else:
        config = {
            "model": dict(TINY, max_len=64), "param_dtype": "float32",
            "compute_dtype": "float32", "cache_dtype": "float32",
            "matmul_precision": "highest", "control_quant": "bf16_3x", "slots": 6, "length": 64,
            "server": {"max_batch": 4, "tick_ms": 2.0, "buckets": [1, 2, 4]}}
        workload = {
            "driver": "chipbench.drivers.serve_closed:run",
            "check": {"sample_episodes": 3, "limits": SERVE_LIMITS},
            "traffic": {"clients": 3, "prefix_lengths": [8, 16],
                        "steps_min": 8, "steps_max": 24, "ramp_s": 0.3,
                        "rpc_timeout_ms": 60000}}
    ctx = types.SimpleNamespace(
        cell={"name": "tiny." + kind, "chips": 1}, workload=workload,
        config=config, peaks=PEAKS, seed=seed, seconds=1.5, trace=False,
        control=False, fault=None, t_start=time.monotonic())
    for k, v in over.items():
        setattr(ctx, k, v)
    return ctx


def _drive(ctx):
    return run._resolve(ctx.workload["driver"])(ctx)


def _line(kind, obs, ctx):
    """The result line as run.py builds it, from the repo's own metric
    entries for the cell of that kind."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w["name"] for w in bench["workloads"]
                if w["traffic"].startswith(kind))
    resolved = run.resolve_cell(cell)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return json.loads(json.dumps(run.result_line(resolved, obs, ctx, device)))


# -- the drivers, end to end -----------------------------------------------------------


@pytest.mark.parametrize("kind,trace", [("train", False), ("train", True),
                                        ("serve", False), ("serve", True)])
def test_driver_end_to_end_prints_the_contracts_keys(kind, trace):
    ctx = _ctx(kind, trace=trace)
    obs = _drive(ctx)
    assert obs["checks"].correct, obs["checks"].rows
    assert obs["failed"] == 0 and obs["attempted"] > 0
    assert obs["compiles_in_window"] == 0
    line = _line(kind, obs, ctx)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks" and line["correct"] is True
    names = set(line["metrics"])
    if trace:
        assert "setup_s" not in names and any("." in n for n in names)
        # a CPU trace has no device plane: the idle share is left out, never 0
        assert not any(n.startswith("device.idle_pct") for n in names)
    else:
        assert "setup_s" in names and len(names) >= 2
    # counts of what need not happen in 1.5 s at three clients (the queue
    # found empty, a launch behind a tick in flight, a bucket missed): 0 is
    # a reading there, and on the chip all three are above it
    may_read_zero = {"serve.idle_wait_ms", "serve.tick_overlap_pct",
                     "serve.batch_pad_pct"}
    for name, m in line["metrics"].items():
        assert m["unit"]
        assert m["value"] > 0 or (name in may_read_zero
                                  and m["value"] == 0), name


@pytest.mark.parametrize("kind,fault,fails", [
    ("train", "state_unchanged", "grad_norm_gap"),
    ("train", "half_batch", "grad_norm_gap"),
    ("serve", "answer_altered", "pred_gap_max"),
])
def test_a_broken_timed_path_is_not_correct(kind, fault, fails):
    obs = _drive(_ctx(kind, fault=fault))
    assert not obs["checks"].correct
    failed = {r["name"] for r in obs["checks"].rows
              if not r["value"] <= r["limit"]}
    assert fails in failed, obs["checks"].rows


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_the_control_in_lower_precision_is_not_correct(kind):
    obs = _drive(_ctx(kind, control=True))
    assert not obs["checks"].correct, obs["checks"].rows


def test_same_seed_same_inputs_and_large_seeds():
    from chipbench.traffic import closed_loop_clients as gen
    from chipbench.traffic import episode_producer

    seed = 2**31 + 12345
    a = episode_producer.episode(seed, 1, 7, 17, 4, 0.5, 1.5)
    b = episode_producer.episode(seed, 1, 7, 17, 4, 0.5, 1.5)
    c = episode_producer.episode(seed, 1, 8, 17, 4, 0.5, 1.5)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    spec = {"prefix_lengths": [8, 16], "steps_min": 8, "steps_max": 24,
            "obs_dim": 4, "clients": 3}
    shapes = {s: sorted((len(gen.episode_plan(spec, s, 0, i)[0]),
                         len(gen.episode_plan(spec, s, 0, i)[1]))
                        for i in range(len(gen.shape_grid(spec))))
              for s in (seed, 3)}
    assert shapes[seed] == shapes[3] == sorted(gen.shape_grid(spec))
    p1 = reference.make_params(dict(TINY, max_len=16), seed)
    p2 = reference.make_params(dict(TINY, max_len=16), seed)
    p3 = reference.make_params(dict(TINY, max_len=16), seed - 2**31)
    assert np.array_equal(p1["head"]["w"], p2["head"]["w"])
    assert not np.array_equal(p1["head"]["w"], p3["head"]["w"])


# -- run.py: no CPU mode, and cells found through files alone --------------------------


def test_run_refuses_a_platform_that_is_not_a_tpu():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", bench["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert out.returncode != 0
    assert "not a TPU" in out.stderr and "{" not in out.stdout


def test_run_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "x", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert out.returncode != 0 and out.stdout == ""


def test_every_cell_resolves_to_files_and_every_metric_to_a_reader():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert bench["paths"] == ["chipbench"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        r = run.resolve_cell(cell["name"])
        assert callable(run._resolve(r.workload["driver"]))
        assert r.config["model"]["d_model"] and r.workload["traffic"]
        names = {m["name"] for m in r.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and r.per_layer
        for m in r.end_to_end + r.per_layer:
            assert callable(run._resolve(m["reader"])), m
        for m in r.per_layer:
            assert m["moves"] in names and m["moves"] in e2e
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_a_cell_added_as_files_only_resolves(tmp_path):
    """A later PR adds a cell, a configuration, a metric with its reader and
    a driver as new files and new entries: nothing that is there is edited."""
    root = tmp_path
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    bench = json.load(open(root / "BENCHMARK.json"))
    base = root / "chipbench"
    (base / "configs" / "new_cfg.json").write_text(json.dumps(
        {"model": dict(TINY, max_len=16)}))
    (base / "traffic" / "new_mix.json").write_text(json.dumps({"clients": 2}))
    (base / "workloads" / "new.cell.json").write_text(json.dumps(
        {"driver": "chipbench.drivers.new_driver:run", "check": {}}))
    (base / "metrics" / "new.metric.json").write_text(json.dumps(
        {"name": "new.metric", "reader": "chipbench.readers.new_reader:read"}))
    bench["configs"].append({"name": "new_cfg", "source": "x", "reduced": [],
                             "file": "chipbench/configs/new_cfg.json",
                             "why": "x"})
    bench["workloads"].append({"name": "new.cell", "config": "new_cfg",
                               "traffic": "new_mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({
        "name": "new.metric", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "device",
        "moves": "setup_s", "workloads": ["new.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = run.resolve_cell("new.cell", root=str(root))
    assert r.config["model"]["d_model"] == 32
    assert r.workload["traffic"] == {"clients": 2}
    assert r.workload["driver"] == "chipbench.drivers.new_driver:run"
    assert [m["name"] for m in r.per_layer] == ["new.metric"]
    assert [m["name"] for m in r.end_to_end] == ["setup_s"]
    # a reader that finds nothing to read is left out of the line
    assert run.read_metrics(
        [{"name": "device.idle", "unit": "%",
          "reader": "chipbench.readers.shared:device_idle_pct"}],
        {"trace": None}, None) == {}


# -- the yardstick's arithmetic ----------------------------------------------------------


def test_flops_and_bytes_against_hand_worked_values():
    # a layer: q, k, v, o 4*1024^2 and the MLP 2*1024*4096 = 12,582,912;
    # eight of them and embed + head 2*32*1024 = 100,728,832
    assert flops.matmul_params(FULL) == 100_728_832
    # forward per token: 2 * 100,728,832 + 8 layers * 2 * 512 * 1024
    assert flops.forward_flops_per_token(FULL, 512) == 209_846_272
    assert flops.train_flops_per_token(FULL, 512) == 629_538_816
    # one decode step at position 299 (300 live positions): scores and apply
    # 4 * 300 * 1024 a layer
    assert flops.decode_flops(FULL, 1, 300) == 201_457_664 + 8 * 1_228_800
    # biases, LayerNorms and the 512 x 1024 position table on top
    assert flops.param_count(FULL) == 101_362_720
    # a tick: every parameter once (4 B each), and K and V of 300 positions
    # at 2 * 1024 * 4 B * 8 layers = 65,536 B a position
    assert flops.decode_bytes(FULL, 1, 300) == 405_450_880 + 19_660_800
    assert flops.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.load_peaks("TPU v9")
    with pytest.raises(KeyError):
        flops.load_peaks("_source")


@pytest.mark.parametrize("intervals,busy,gaps", [
    ([], 0.0, [(0.0, 40.0)]),
    ([(0, 10), (5, 12), (20, 30)], 22.0, [(12.0, 20.0), (30.0, 40.0)]),
    ([(0, 10), (2, 3), (4, 9)], 10.0, [(10.0, 40.0)]),        # nested
    ([(5, 5), (10, 20), (20, 25)], 15.0, [(0.0, 10.0), (25.0, 40.0)]),
])
def test_trace_reduce_on_synthetic_intervals(intervals, busy, gaps):
    assert trace_reduce.busy_seconds(intervals) == busy
    assert trace_reduce.idle_gaps(intervals, 0, 40) == gaps


def test_trace_reduce_names_ops_and_what_the_host_did_in_a_gap():
    out = trace_reduce.reduce_events(
        {"d0": [("matmul", 0, 10e9), ("copy", 5e9, 7e9), ("matmul", 20e9,
                                                          10e9)]},
        [("thread", 0, 40e9), ("fence", 12e9, 7e9), ("blip", 31e9, 1e9),
         ("reply", 29e9, 11e9)],
        window=(0, 40e9))
    assert out["busy_s"] == 22.0 and out["window_s"] == 40.0
    assert out["device_ops"] == [["matmul", 20.0], ["copy", 7.0]]
    # 12..20 lies under `fence` (the shortest span over half of it), 30..40
    # under `reply`; the thread's own span covers the whole window and
    # names nothing
    assert out["idle_gaps"] == [["reply", 10.0], ["fence", 8.0]]
    bare = trace_reduce.reduce_events(
        {"d0": [("matmul", 0, 10e9), ("matmul", 30e9, 10e9)]},
        [("thread", 0, 40e9)], window=(0, 40e9))
    assert bare["idle_gaps"] == [["unattributed", 20.0]]
    assert trace_reduce.reduce_events({}, []) is None


# -- the reference against the program, float32 on the CPU ---------------------------


def test_reference_agrees_with_seqformer_apply_and_prefill_then_decode():
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel

    model = dict(TINY, max_len=32)
    params = reference.make_params(model, 11)
    rng = np.random.default_rng(0)
    obs = rng.standard_normal((2, 24, 4)).astype(np.float32)
    want = np.asarray(reference.forward(params, obs))
    got = np.asarray(seqformer.apply(params, obs, compute_dtype=jnp.float32))
    assert np.abs(got - want).max() < 1e-5
    served = SeqFormerModel(params, slots=2, length=32)
    preds = [served.prefill_rows(np.asarray([1]), obs[0, :16])]
    for t in range(16, 24):
        preds.append(served.step_rows(np.asarray([1]), obs[0, t:t + 1])[0])
    assert np.abs(np.stack(preds) - want[0, 15:]).max() < 1e-5
    # and its loss with episode_loss_fn
    ep = rng.standard_normal((2, 17, 4)).astype(np.float32)
    loss = float(reference.sum_sq_error(params, ep)) / (2 * 16 * 4)
    prog = float(seqformer.episode_loss_fn(
        params, {"episode": ep}, compute_dtype=jnp.float32))
    assert abs(loss - prog) < 1e-5 * abs(prog)
    assert len(jax.tree.leaves(params)) == 7 + 16 * model["n_layers"]
