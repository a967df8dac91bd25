"""The benchmark's inputs and its check, on the CPU at tiny sizes: the
generators, the bulk loader against the reference, and ``bench/run.py``
end to end in the interpret-mode rehearsal, with the control and the
planted faults that the check must fail."""

import types

import numpy as np
import pytest

from bench import control, load, reference
from bench import run as harness
from bench.gen import nrxys
from repro.core import plane_check as pc
from repro.core import splaylist as sx

PAPER_TINY = {"config": {"n": 2000, "key_space": 4000, "capacity": 2050,
                         "width": 2048, "levels": 12,
                         "history_reads": 20000},
              "traffic": {"batch": 256}}
RESIZE = {"paper-ro-99-1": PAPER_TINY, "paper-rw-90-10": PAPER_TINY,
          "paper-ro-100-100": PAPER_TINY}
SEED = 2 ** 31 + 12345      # seeds past 32 signed bits are allowed


def _streams():
    paper_cfg = {"n": 2000, "key_space": 4000, "prepopulate": 0.9,
                 "p": 0.01}
    yield nrxys.Stream(paper_cfg, {"batch": 256, "r": 1.0, "x": 0.99,
                                   "y": 0.01, "s": 0.25},
                       np.random.default_rng(4)), True
    yield nrxys.Stream(paper_cfg, {"batch": 256, "r": 0.9, "x": 0.9,
                                   "y": 0.1, "s": 0.25},
                       np.random.default_rng(5)), False


@pytest.mark.parametrize("which", [0, 1])
def test_bulk_load_audits_and_answers_like_the_reference(which):
    stream, read_only = list(_streams())[which]
    rng = np.random.default_rng(7)
    hits = 1 + load.prior_hits(stream, stream.keys, 20000, 0.05, rng)
    assert hits.sum() > len(stream.keys)
    st, plane = load.bulk_load(stream.keys, hits, 2050, 12, 2048)
    assert pc.audit_ok(pc.audit_plane(st, plane))
    assert int(st.size) == len(stream.keys)
    assert (np.asarray(plane.keys)[-1, :len(stream.keys)]
            == stream.keys).all()
    # the loaded heights follow the hits: keys hit in the history stand
    # taller than keys that were not
    heights = np.asarray(plane.heights)[:len(stream.keys)]
    assert heights[hits > 1].mean() > heights[hits == 1].mean() + 1
    got_keys, got_hits = harness.live_keys(st)
    assert (got_keys == stream.keys).all() and (got_hits == hits).all()
    ref = reference.KeySet(stream.keys, hits)
    flags = ({"aggregate": True, "plane_search": True} if read_only
             else {})
    for _ in range(3):
        kinds, keys, upd = stream.next_batch()
        st, plane, res, *_ = sx.run_serving(st, plane, kinds[None],
                                            keys[None], upd[None], **flags)
        assert (np.asarray(res)[0] == ref.apply(kinds, keys, upd)).all()
    got_keys, got_hits = harness.live_keys(st)
    assert (got_keys == ref.sorted_keys()).all()
    settled, want_hits = ref.settled_hits()
    assert len(settled) > 0.9 * len(got_keys)
    assert (got_hits[np.searchsorted(got_keys, settled)] == want_hits).all()
    loaded = dict(zip(stream.keys.tolist(), hits.tolist()))
    assert any(h > loaded.get(k, 0)
               for k, h in zip(settled.tolist(), want_hits.tolist()))


def test_reference_applies_lanes_in_order():
    ref = reference.KeySet([5, 9])
    kinds = np.array([0, 1, 0, 2, 0, 2, 1, 1])
    keys = np.array([7, 7, 7, 9, 9, 9, 9, 9])
    assert ref.apply(kinds, keys).tolist() == [0, 1, 1, 1, 0, 0, 1, 0]
    assert ref.sorted_keys().tolist() == [5, 7, 9]
    assert ref.apply(np.zeros(3), np.array([5, 6, 7])).tolist() == [1, 0, 1]
    with pytest.raises(ValueError):
        ref.apply(np.array([3]), np.array([1]))


def test_reference_counts_hits_of_live_keys():
    ref = reference.KeySet([5, 9, 11], [3, 1, 2])
    # reads: coins on live keys count, on absent keys nothing
    ref.apply(np.zeros(5), np.array([5, 5, 6, 9, 11]),
              np.array([True, True, True, False, True]))
    # writes: a coin on an insert of a live key counts; a new key starts
    # at 1; a deleted key's count is no longer settled, even revived
    ref.apply(np.array([1, 1, 2, 1, 0]), np.array([5, 7, 9, 9, 7]),
              np.array([True, False, True, False, True]))
    keys, hits = ref.settled_hits()
    assert keys.tolist() == [5, 7, 11]
    assert hits.tolist() == [6, 2, 3]
    assert ref.sorted_keys().tolist() == [5, 7, 9, 11]


def test_main_refuses_without_a_tpu(capsys):
    assert harness.main(["--workload", "paper-ro-99-1", "--seed", "1",
                         "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


@pytest.mark.parametrize("workload", sorted(RESIZE))
def test_rehearsal_end_to_end(workload):
    result, info = harness.rehearse(workload, SEED, 0.5, False,
                                    RESIZE[workload])
    assert result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0
    assert result["attempted"] == info["batches"] * 256 > 0
    assert set(result["metrics"]) == {"ops_per_s", "batch_p90_ms",
                                      "setup_s"}
    assert all(v["limit"] == 0 for v in result["compared"].values())
    assert info["window_compiles"] == 0


def test_rehearsal_traced_reads_the_program_counters():
    result, info = harness.rehearse("paper-rw-90-10", SEED, 0.5, True,
                                    PAPER_TINY)
    assert result["correct"] is True
    # no TPU plane on the CPU: only the program counter has something
    # to read, and no device metric is written
    assert set(result["metrics"]) == {"walk_steps_per_op"}
    assert result["metrics"]["walk_steps_per_op"]["value"] > 1


def _unchanged(st, pl, *args, **kw):
    return (st, pl) + tuple(sx.run_serving(st, pl, *args, **kw)[2:])


def _half_batch(st, pl, kinds, keys, upd, **kw):
    half = kinds.shape[1] // 2
    kinds = kinds.copy()
    kinds[:, half:] = sx.OP_CONTAINS       # the second half is not served
    upd = upd.copy()
    upd[:, half:] = False
    out = sx.run_serving(st, pl, kinds, keys, upd, **kw)
    res = out[2].at[:, half:].set(0)
    return out[:2] + (res,) + tuple(out[3:])


def _altered_answer(st, pl, *args, **kw):
    out = sx.run_serving(st, pl, *args, **kw)
    res = out[2].at[0, 0].set(1 - out[2][0, 0])
    return out[:2] + (res,) + tuple(out[3:])


@pytest.mark.parametrize("workload, fault, caught_by", [
    ("paper-rw-90-10", _unchanged, "key_set_mismatches"),
    # read-only: answers, key set and bottom row cannot show a state
    # that the fold left as it was; the hit counters do
    ("paper-ro-99-1", _unchanged, "hit_count_mismatches"),
    ("paper-ro-100-100", _unchanged, "hit_count_mismatches"),
    ("paper-rw-90-10", _half_batch, "answer_mismatches"),
    ("paper-ro-99-1", _half_batch, "answer_mismatches"),
    ("paper-rw-90-10", _altered_answer, "answer_mismatches"),
    ("paper-ro-99-1", _altered_answer, "answer_mismatches"),
    ("paper-ro-100-100", _altered_answer, "answer_mismatches"),
])
def test_planted_faults_fail_the_check(workload, fault, caught_by):
    result, _ = harness.rehearse(workload, SEED + 1, 0.2, False,
                                 RESIZE[workload], serve=fault)
    assert result["correct"] is False
    assert result["compared"][caught_by]["value"] > 0


@pytest.mark.parametrize("workload", sorted(RESIZE))
def test_control_with_narrowed_keys_fails_the_check(workload):
    """At the cells' sizes the control keeps 16 bits of a key space of
    2^17.6; the tiny key spaces here need 8 bits to collide as surely."""
    width = RESIZE[workload]["config"]["width"]
    result, _ = harness.rehearse(
        workload, SEED + 2, 0.2, False, RESIZE[workload],
        serve=control.KeyPrecisionControl(width, bits=8))
    assert result["correct"] is False
    compared = result["compared"]
    assert (compared["answer_mismatches"]["value"] > 0
            or compared["key_set_mismatches"]["value"] > 0)



def test_hit_counts_match_the_reference_through_rebuilds():
    """Heavy churn with most coins set: deletes reclaim marked nodes in
    rebuilds, and every settled key's counter still equals the
    reference's."""
    stream = nrxys.Stream({"n": 300, "key_space": 600, "prepopulate": 0.9,
                           "p": 0.5},
                          {"batch": 128, "r": 0.0, "x": 0.9, "y": 0.1,
                           "s": 0.25}, np.random.default_rng(1))
    hits = np.ones(len(stream.keys), np.int32)
    st, plane = load.bulk_load(stream.keys, hits, 514, 10, 512)
    ref = reference.KeySet(stream.keys, hits)
    shrinks, n_alloc = 0, int(st.n_alloc)
    for _ in range(12):
        kinds, keys, upd = stream.next_batch()
        st, plane, res, *_ = sx.run_serving(st, plane, kinds[None],
                                            keys[None], upd[None])
        assert (np.asarray(res)[0] == ref.apply(kinds, keys, upd)).all()
        shrinks += int(st.n_alloc) < n_alloc
        n_alloc = int(st.n_alloc)
    assert shrinks >= 1                     # a rebuild reclaimed nodes
    got_keys, got_hits = harness.live_keys(st)
    settled, want = ref.settled_hits()
    assert len(settled) > len(got_keys) // 2
    assert (got_hits[np.searchsorted(got_keys, settled)] == want).all()


def test_traced_run_that_reads_no_layer_prints_no_result(monkeypatch,
                                                          capsys):
    """A traced run whose trace gives a listed per-layer metric nothing
    to read (here: no device plane at all) exits 3 with no result line,
    rather than leaving the metric out."""
    spec = harness.read_json(harness.ROOT, "BENCHMARK.json")
    kind = next(iter(harness.read_json(harness.BENCH,
                                       "peaks.json")["devices"]))
    fake = types.SimpleNamespace(platform="tpu", device_kind=kind)
    cell_spec = harness.cell_spec

    def tiny(spec, name):
        cell, config, traffic = cell_spec(spec, name)
        return (cell, {**config, **PAPER_TINY["config"]},
                {**traffic, **PAPER_TINY["traffic"]})

    monkeypatch.setattr(harness, "check_device", lambda chips, peaks: fake)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "cell_spec", tiny)
    assert any(w["name"] == "paper-ro-99-1" for w in spec["workloads"])
    assert harness.main(["--workload", "paper-ro-99-1", "--seed", "5",
                         "--seconds", "0.2", "--trace", "1"]) == 3
    out = capsys.readouterr()
    assert out.out == ""
    assert "gave nothing to read" in out.err
