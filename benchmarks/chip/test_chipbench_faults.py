"""The check that decides ``correct``: a sound run passes; a run with the
timed path broken underneath, or the control in the program's place,
fails.  The harness runs here on the CPU, past its look for a chip, at a
size the CPU runs in a second."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, spec
from chipbench_testing import HERE, REPO, SRC, run_cell, tiny_root
import control

from repro.core import engine, state as S, sweep

REAL_RUN, REAL_GRID = engine.run, sweep.run_grid


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """The tiny checkout; no persistent compile cache; JAX as it was."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield tiny_root(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def no_trips(shape=()):
    """Loop counters of a run that made no loop trip."""
    zeros = jnp.zeros(shape, jnp.int32)
    return engine.RunStats(iterations=zeros, events=zeros)


def unchanged_grid(batch, vm_p, task_p, **_):
    """A step that returns its state unchanged, under every policy; the
    counters (asked for with ``stats=True``) count no trip."""
    n = vm_p.shape[0]
    out = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                       batch)
    return out, no_trips((n, batch.time.shape[0]))


def half_grid(batch, vm_p, task_p, **kw):
    """Only the first half of the replicates run; the rest come back as
    they went in."""
    h = batch.time.shape[0] // 2
    ran = REAL_GRID(jax.tree.map(lambda x: x[:h], batch), vm_p, task_p, **kw)
    rest = unchanged_grid(jax.tree.map(lambda x: x[h:], batch), vm_p, task_p)
    return jax.tree.map(lambda a, b: jnp.concatenate(
        [a, jax.device_put(b, a.sharding)], axis=1), ran, rest)


def no_exchange_grid(batch, vm_p, task_p, **kw):
    """The lane axis in four blocks, as a mesh of four chips holds it,
    with the gather left out: every block comes back holding the first
    chip's."""
    out = REAL_GRID(batch, vm_p, task_p, **kw)

    def first_chip_everywhere(x):
        flat = x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
        block = flat[:flat.shape[0] // 4]
        return jnp.concatenate([block] * 4).reshape(x.shape)
    return jax.tree.map(first_chip_everywhere, out)


def altered(result):
    """Every completed cloudlet's finish time 1% late, where it is made."""
    out, stats = result
    cl = out.cloudlets
    ft = jnp.where(cl.state == S.CL_DONE, cl.finish_time * 1.01,
                   cl.finish_time)
    return dataclasses.replace(out, cloudlets=dataclasses.replace(
        cl, finish_time=ft)), stats


@pytest.mark.parametrize("workload", ["sweep.grid", "fig89.single"])
def test_sound_run_is_correct(tiny, workload):
    result = run_cell(*tiny, workload)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"setup_s", "cloudlets_per_s"}
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"


GRID_FAULTS = {
    "state_unchanged": unchanged_grid,
    "half_batch": half_grid,
    "no_exchange": no_exchange_grid,
    "answer_altered": lambda *a, **k: altered(REAL_GRID(*a, **k)),
}


@pytest.mark.parametrize("fault", sorted(GRID_FAULTS))
def test_grid_fault_is_caught(tiny, monkeypatch, fault):
    monkeypatch.setattr(sweep, "run_grid", GRID_FAULTS[fault])
    result = run_cell(*tiny, "sweep.grid")
    assert result["correct"] is False, (fault, result["checks"])


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_single_run_fault_is_caught(tiny, monkeypatch, fault):
    fake = {"state_unchanged": lambda dc, **_: (dc, no_trips()),
            "answer_altered": lambda dc, **k: altered(REAL_RUN(dc, **k))}
    monkeypatch.setattr(engine, "run", fake[fault])
    result = run_cell(*tiny, "fig89.single")
    assert result["correct"] is False, (fault, result["checks"])


FOUR_DEVICE_RUNS = """
import json, pathlib, sys
import test_chipbench_faults as t
out = {"sound": t.run_cell(*t.tiny_root(pathlib.Path(sys.argv[1])),
                           "sweep.grid_4chip")}
for name, fault in sorted(t.GRID_FAULTS.items()):
    t.sweep.run_grid = fault
    try:
        out[name] = t.run_cell(*t.tiny_root(pathlib.Path(sys.argv[1]) / name),
                               "sweep.grid_4chip")
    finally:
        t.sweep.run_grid = t.REAL_GRID
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_device_runs(tmp_path_factory):
    """The four-chip cell's tiny runs on four CPU devices, in a process of
    their own (JAX fixes its device count when it starts): a sound run,
    and one under each grid fault, on ``run_grid``'s default partitioner
    over a mesh of four."""
    tmp = tmp_path_factory.mktemp("four_devices")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"),
               PYTHONPATH=os.pathsep.join([HERE, SRC]))
    proc = subprocess.run([sys.executable, "-c", FOUR_DEVICE_RUNS, str(tmp)],
                          env=env, cwd=HERE, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_chip_sound_run_is_correct(four_device_runs):
    result = four_device_runs["sound"]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"]["count"] == 4
    assert set(result["metrics"]) == {"setup_s", "cloudlets_per_s"}


@pytest.mark.parametrize("fault", sorted(GRID_FAULTS))
def test_four_chip_fault_is_caught(four_device_runs, fault):
    result = four_device_runs[fault]
    assert result["correct"] is False, (fault, result["checks"])


@pytest.mark.parametrize("workload", ["sweep.grid", "fig89.single"])
def test_control_is_refused(tiny, workload):
    """The reference in bfloat16 in the program's place fails a number."""
    root, bench_dir = tiny
    cell = spec.load_cell(root, workload, bench_dir)
    for seed in (1, 2**31 + 3, 77):
        correct, table = control.control(cell, seed, n_studies=2)
        assert correct is False, table
        assert table["time_rel_err"]["value"] > \
            table["time_rel_err"]["limit"]


def test_traced_run_reports_per_layer_metrics(tiny):
    result = run_cell(*tiny, "fig89.single", trace=1)
    assert result["correct"] is True
    assert {"build_s", "compile_s"} <= set(result["metrics"])
    assert "cloudlets_per_s" not in result["metrics"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["device"]["window_s"] > 0.0


def test_refuses_without_a_tpu(capsys):
    """On the CPU the harness refuses: no result, exit code 3."""
    with pytest.raises(harness.NoChip):
        run_cell(REPO, spec.BENCH_DIR, "fig89.single", require_tpu=True)
    rc = harness.main(["--workload", "fig89.single", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], 0.0)
    assert rc == 3
    out = capsys.readouterr().out
    assert not any(line.startswith("{") for line in out.splitlines())
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.strip().splitlines()[-1] if out.strip() else "")
