"""The one trusted persist of worker/train.py (`persist_trusted_params`):
the scalar and the population site run the same dump / serialize / write
sequence, with the same spans, attributes, counters and faults; what lands on
disk is the frame around flax's msgpack bytes."""

import glob
import os

import numpy as np
import pytest

from rafiki_tpu.sdk import artifact
from rafiki_tpu.sdk.log import ModelLogger
from rafiki_tpu.sdk.params import dump_params, load_params
from rafiki_tpu.utils.metrics import REGISTRY
from rafiki_tpu.utils.trace import Tracer
from rafiki_tpu.worker import faults
from rafiki_tpu.worker.faults import FaultKind
from rafiki_tpu.worker.train import TrainWorker

JOB = {"train_dataset_uri": "", "test_dataset_uri": ""}


def _tree(i: int) -> dict:
    rng = np.random.default_rng(i)
    return {"dense": {"w": rng.standard_normal((50, 40)).astype(np.float32),
                      "b": np.full((40,), i, np.float32)},
            # not contiguous: the one leaf persist has to copy
            "t": rng.standard_normal((8, 6)).astype(np.float32).T,
            "member": i}


COPIED = 8 * 6 * 4


class _Scalar:
    def __init__(self, **knobs):
        pass

    def train(self, uri):
        pass

    def evaluate(self, uri):
        return 0.5

    def dump_parameters(self):
        return _tree(0)

    def destroy(self):
        pass


class _Population(_Scalar):
    def train_population(self, uri, member_knobs):
        self.k = len(member_knobs)

    def evaluate_population(self, uri):
        return [0.1 * (i + 1) for i in range(self.k)]

    def dump_member_parameters(self, i):
        return _tree(i)


def _worker(tmp_path) -> TrainWorker:
    from rafiki_tpu.advisor.advisor import AdvisorStore
    from rafiki_tpu.db.database import Database

    return TrainWorker("sub-p", Database(":memory:"), AdvisorStore(),
                       params_dir=str(tmp_path / "params"))


def _counters() -> tuple:
    return tuple(
        REGISTRY.counter(f"rafiki_params_persist_{k}_total").value()
        for k in ("bytes", "copied_bytes"))


def _run(site: str, tmp_path, tracer: Tracer) -> list:
    """Run one trial through `site`; returns [(params_path, error, tree)]."""
    worker = _worker(tmp_path)
    if site == "scalar":
        _, path = worker._run_trial(_Scalar, {}, JOB, "t0", ModelLogger(),
                                    tracer)
        return [(path, None, _tree(0))]
    results = worker._run_population_trial(
        _Population, [("t0", {}), ("t1", {})], JOB, ModelLogger(), tracer)
    return [(path, err, _tree(i))
            for i, (_, _, _, path, err) in enumerate(results)]


@pytest.mark.parametrize("site", ["scalar", "population"])
def test_both_sites_emit_the_three_spans_and_count_the_bytes(
        site, tmp_workdir):
    tracer = Tracer(f"trial-{site}")
    before = _counters()
    persisted = _run(site, tmp_workdir, tracer)
    payloads = []
    for path, err, tree in persisted:
        assert err is None
        payload = artifact.read_artifact(path)
        assert payload == dump_params(tree)  # flax's bytes, framed
        np.testing.assert_array_equal(load_params(payload)["t"], tree["t"])
        payloads.append(len(payload))
    spans = sorted(tracer.spans, key=lambda s: s.start)
    whole = [s for s in spans if s.name == "persist_params"]
    assert len(whole) == 1 and whole[0].depth == 0
    steps = [s for s in spans if s.name.startswith("persist.")]
    assert [s.name for s in steps] == [
        "persist.dump", "persist.serialize", "persist.write"] * len(persisted)
    assert all(s.depth == 1 and whole[0].start <= s.start
               and s.end <= whole[0].end for s in steps)
    writes = [s for s in steps if s.name == "persist.write"]
    assert [s.attrs for s in writes] == [
        {"bytes": n, "copied_bytes": COPIED} for n in payloads]
    assert "attrs" in writes[0].to_dict()  # saved with the trial's trace
    after = _counters()
    assert after[0] - before[0] == sum(payloads)
    assert after[1] - before[1] == COPIED * len(persisted)
    assert glob.glob(str(tmp_workdir / "params" / "*.tmp")) == []


def _full_disk(monkeypatch, fail_on: str) -> None:
    """`write_artifact` as worker/train.py calls it, with a full disk under
    the file named `fail_on`."""
    real = artifact.write_artifact

    def write(path, payload, mode=None):
        if os.path.basename(path) == fail_on:
            raise OSError(28, "No space left on device")
        return real(path, payload, mode=mode)

    monkeypatch.setattr("rafiki_tpu.worker.train.write_artifact", write)


def test_write_oserror_is_the_infra_fault_at_the_scalar_site(
        tmp_workdir, monkeypatch):
    _full_disk(monkeypatch, "t0.params")
    before = _counters()
    with pytest.raises(faults.TrialFault, match="params persist failed") as e:
        _run("scalar", tmp_workdir, Tracer("trial-f"))
    assert e.value.kind == FaultKind.INFRA
    assert isinstance(e.value.__cause__, OSError)
    assert faults.classify_failure(e.value)[0] == FaultKind.INFRA
    assert _counters() == before  # nothing was persisted, nothing counted


def test_write_oserror_is_one_members_fault_at_the_population_site(
        tmp_workdir, monkeypatch):
    _full_disk(monkeypatch, "t1.params")
    (path0, err0, tree0), (path1, err1, _) = _run(
        "population", tmp_workdir, Tracer("trial-g"))
    assert err0 is None
    assert artifact.read_artifact(path0) == dump_params(tree0)
    assert path1 is None and isinstance(err1, faults.TrialFault)
    assert err1.kind == FaultKind.INFRA
    assert "params persist failed" in str(err1)


def test_a_dump_that_raises_is_the_templates_at_both_sites(tmp_workdir):
    """What `dump` raises passes through untouched: a USER-class error at
    the scalar site, that member's own exception at the population site
    (an OSError there keeps its INFRA reading, as before PR 28)."""
    class BadScalar(_Scalar):
        def dump_parameters(self):
            raise KeyError("no such layer")

    class BadMember(_Population):
        def dump_member_parameters(self, i):
            if i == 0:
                raise KeyError("no such layer")
            if i == 1:
                raise OSError("template's own file is gone")
            return _tree(i)

    worker = _worker(tmp_workdir)
    with pytest.raises(KeyError):
        worker._run_trial(BadScalar, {}, JOB, "t0", ModelLogger(),
                          Tracer("trial-h"))
    results = worker._run_population_trial(
        BadMember, [("t0", {}), ("t1", {}), ("t2", {})], JOB, ModelLogger(),
        Tracer("trial-i"))
    assert isinstance(results[0][4], KeyError)
    assert isinstance(results[1][4], faults.TrialFault)
    assert results[1][4].kind == FaultKind.INFRA
    assert results[2][4] is None and os.path.exists(results[2][3])
