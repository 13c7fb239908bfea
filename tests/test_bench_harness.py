"""``benchmarks/bench_policies.main``: one process per device, and a failed
phase fails the run.  The children are replaced by fakes, so nothing here
touches a device or spends minutes benchmarking."""
import json

import pytest

from benchmarks import bench_policies as bp

TPU_META = {"platform": "tpu", "device_kind": "TPU v5 lite",
            "device_count": 1}
CPU_META = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}


class FakeChildren:
    """Stands in for ``bp._worker``: records each child started and
    answers from ``replies`` (an exception instance is raised)."""

    def __init__(self):
        self.started = []
        self.replies = {}

    def __call__(self, args, marker, *, env=None, timeout):
        self.started.append(args[0])
        reply = self.replies[args[0]]
        if isinstance(reply, Exception):
            raise reply
        return reply


@pytest.fixture
def children(tmp_path, monkeypatch):
    fake = FakeChildren()
    fake.json_path = tmp_path / "BENCH_policies.json"
    fake.streaming = {"10000": {"streamed": {"wall_s": 1.0},
                                "resident": {"wall_s": 2.0}}}
    monkeypatch.setattr(bp, "_JSON_PATH", str(fake.json_path))
    monkeypatch.setattr(bp, "_worker", fake)
    monkeypatch.setattr(bp, "bench_streaming", lambda: fake.streaming)
    return fake


def test_accelerator_runs_sharded_in_the_device_child(children):
    children.replies["--device-phases"] = {"meta": dict(TPU_META),
                                           "sharded": {"devices": 1}}
    assert bp.main() == 0
    assert children.started == ["--device-phases"]
    written = json.loads(children.json_path.read_text())
    assert written["meta"]["platform"] == "tpu"
    assert written["meta"]["device_count"] == 1
    assert written["sharded"] == {"devices": 1}


def test_cpu_runs_sharded_in_a_forced_device_child(children):
    children.replies["--device-phases"] = {"meta": dict(CPU_META)}
    children.replies["--sharded-worker"] = {"devices": 2}
    assert bp.main() == 0
    assert children.started == ["--device-phases", "--sharded-worker"]


@pytest.mark.parametrize("broken", ["device", "streaming", "sharded"])
def test_failed_phase_fails_the_run(children, broken):
    children.replies["--device-phases"] = (
        bp.WorkerFailed("--device-phases rc=1") if broken == "device"
        else {"meta": dict(CPU_META)})
    children.replies["--sharded-worker"] = (
        bp.WorkerFailed("--sharded-worker rc=1") if broken == "sharded"
        else {"devices": 2})
    if broken == "streaming":
        children.streaming["10000"]["resident"] = {"error": "rc=-9"}
    assert bp.main() == 1
    assert not children.json_path.exists()
