"""The span recorder (``repro.core.spans``) and the spans the save, load and
FE-restart paths record at their layer boundaries."""

from __future__ import annotations

import collections
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spans as S
from repro.core.comm import Comm
from repro.core.jax_io import layout_from_jax, load_jax, save_jax
from repro.core.store import DatasetStore
from repro.core.tensor_ckpt import TensorCheckpoint


def _since(t0: float) -> list[S.Span]:
    return [s for s in S.spans() if s.t0 >= t0]


def _by_id(spans: list[S.Span]) -> dict[int, S.Span]:
    return {s.span_id: s for s in spans}


def _children(spans: list[S.Span], parent: S.Span) -> list[S.Span]:
    return [s for s in spans if s.parent_id == parent.span_id]


def _names(spans) -> collections.Counter:
    return collections.Counter(s.name for s in spans)


# ------------------------------------------------------------ the recorder
def test_parents_nest_per_thread_and_children_take_the_step():
    t0 = time.perf_counter()
    inside = threading.Event()
    release = threading.Event()

    def other():
        with S.span("t.other", step=9):
            with S.span("t.other.child"):
                inside.set()
                release.wait(10)

    th = threading.Thread(target=other, name="span-test-thread")
    with S.span("t.outer", step=4) as outer:
        th.start()
        assert inside.wait(10)
        with S.span("t.inner", bytes=12) as inner:
            inner.attrs["elements"] = 3
        release.set()
        th.join(10)
    assert not th.is_alive()
    got = {s.name: s for s in _since(t0)}
    assert got["t.inner"].parent_id == outer.span_id
    assert got["t.inner"].attrs == {"bytes": 12, "elements": 3, "step": 4}
    assert got["t.outer"].parent_id is None
    assert got["t.other.child"].parent_id == got["t.other"].span_id
    assert got["t.other.child"].attrs == {"step": 9}
    assert got["t.other"].thread == "span-test-thread"
    assert got["t.inner"].thread == threading.current_thread().name
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


def test_a_span_is_recorded_when_its_block_raises():
    t0 = time.perf_counter()
    with pytest.raises(KeyError):
        with S.span("t.raises"):
            raise KeyError("x")
    assert [s.name for s in _since(t0)] == ["t.raises"]
    with S.span("t.after") as after:
        pass
    assert after.parent_id is None          # the stack was unwound


def test_full_ring_drops_the_oldest_and_counts_them(monkeypatch):
    monkeypatch.setattr(S, "_ring", collections.deque(maxlen=4))
    dropped0 = S.dropped()
    count0 = S.totals().get("t.ring", {}).get("count", 0)
    for i in range(10):
        with S.span("t.ring", bytes=i):
            pass
    assert S.dropped() - dropped0 == 6
    assert [s.attrs["bytes"] for s in S.spans()] == [6, 7, 8, 9]
    tot = S.totals()["t.ring"]
    assert tot["count"] - count0 == 10 and tot["bytes"] >= 45


def test_a_fresh_jit_records_one_compile():
    S.record_compiles()
    S.record_compiles()                 # idempotent: one listener
    t0 = time.perf_counter()
    x = np.arange(7 * 13, dtype=np.float32).reshape(7, 13)
    jax.jit(lambda a: a * 3.0 + 1.0)(x).block_until_ready()
    compiles = [s for s in _since(t0 - 60) if s.name == "jax.compile"
                and s.t1 >= t0]
    assert len(compiles) == 1
    assert 0 < compiles[0].seconds < time.perf_counter() - t0


def test_the_host_layers_import_no_jax():
    code = ("import sys; import repro.core.store, repro.core.tensor_ckpt, "
            "repro.core.async_io, repro.fem.checkpoint; "
            "from repro.core.spans import span\n"
            "with span('t.nojax'): pass\n"
            "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=dict(os.environ, PYTHONPATH=str(
            Path(__file__).resolve().parents[1] / "src")),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == "[]"


# ------------------------------------------------------------ the save path
def _state():
    k = jax.random.PRNGKey(3)
    return {"w": jax.random.normal(k, (16, 8), dtype=jnp.float32),
            "b": jnp.arange(5, dtype=jnp.bfloat16),
            "n": jnp.array(5, dtype=jnp.int32)}


class _Pipeline:
    def state(self, step):
        return {"next": int(step)}


def test_async_save_spans_on_both_threads_carry_the_step(tmp_path):
    from repro.train.loop import Trainer, TrainerConfig

    tr = Trainer(None, _Pipeline(),
                 TrainerConfig(ckpt_dir=str(tmp_path / "ck")), lambda: None)
    state = _state()
    t0 = time.perf_counter()
    tr._save(state, 3)
    tr.wait_for_writes()
    t_mid = time.perf_counter()
    tr._save(state, 6)
    tr.wait_for_writes()
    rec = _since(t0)
    ids = _by_id(rec)

    save = next(s for s in rec if s.name == "ckpt.save"
                and s.attrs["step"] == 3)
    snap, = [s for s in _children(rec, save) if s.name == "ckpt.snapshot"]
    kids = _children(rec, snap)
    assert _names(kids) == {"ckpt.snapshot.d2h": 3, "ckpt.snapshot.copy": 3}
    nbytes = sum(int(a.nbytes) for a in state.values())
    assert snap.attrs["bytes"] == nbytes
    assert sum(s.attrs["bytes"] for s in kids
               if s.name == "ckpt.snapshot.d2h") == nbytes
    assert all(s.attrs["step"] == 3 and s.thread == save.thread
               for s in kids)
    stage = {s.name: s for s in _children(rec, save)}
    assert stage["ckpt.stage.pack"].attrs["bytes"] == nbytes
    assert "ckpt.stage.wait" in stage

    # writer thread: begin, state and commit jobs, each with the step
    jobs = [s for s in rec if s.name == "ckpt.writer.job"
            and s.attrs.get("step") == 3]
    assert [j.attrs["label"] for j in jobs] == ["begin/s3", "state/s3",
                                                "commit/s3"]
    assert all(j.thread == "async-ckpt-writer" != save.thread for j in jobs)
    state_job = jobs[1]

    def under(s, root):
        while s.parent_id is not None:
            s = ids.get(s.parent_id)
            if s is None:
                return False
            if s is root:
                return True
        return False

    work = [s for s in rec if under(s, state_job)]
    assert {s.name for s in work} >= {"ckpt.write.concat", "ckpt.write.crc",
                                      "ckpt.store.hash", "ckpt.store.write",
                                      "ckpt.store.flush_meta"}
    assert all(s.attrs["step"] == 3 for s in work)
    commit = [s for s in rec if under(s, jobs[2])]
    assert _names(commit) == {"ckpt.commit": 1, "ckpt.store.flush_meta": 1}

    # one timing: the job log is the job spans
    log = tr._async.job_log
    assert [(e["label"], e["t0"], e["t1"]) for e in log[:3]] == \
        [(j.attrs["label"], j.t0, j.t1) for j in jobs]

    # host passes over the saved bytes on the writer thread, second save:
    # concatenation (1), each chunk's tobytes copy and crc32 scan (2),
    # blake2b over the vec (1) and over the 8-byte crc of each chunk; the
    # sections are carried from the first save, not hashed again
    layout = layout_from_jax(state)
    chunks = sum(spec.grid.num_chunks for spec in layout.arrays)
    later = [s for s in rec if s.t0 >= t_mid and s.attrs.get("pass_")
             and s.thread == "async-ckpt-writer"]
    assert all(s.attrs["step"] == 6 for s in later)
    passes = sum(s.attrs["bytes"] for s in later)
    assert passes == 4 * nbytes + 8 * chunks
    assert _names(later) == {"ckpt.write.concat": 3, "ckpt.write.crc": 3,
                             "ckpt.store.hash": 6}


def test_arena_back_pressure_is_timed_by_its_wait_span():
    from repro.core.async_io import StagingArena

    arena = StagingArena(max_slots=1)
    slot = arena.acquire(64)
    t0 = time.perf_counter()
    timer = threading.Timer(0.05, arena.release, (slot,))
    timer.start()
    arena.acquire(64)                    # blocks until the timer releases
    timer.join()
    waits = [s for s in _since(t0) if s.name == "ckpt.stage.wait"]
    assert len(waits) == 1 and waits[0].seconds >= 0.04
    assert arena.stats.backpressure_hits == 1
    assert arena.stats.blocked_seconds == waits[0].seconds


# ------------------------------------------------------------ the load path
def test_store_read_seconds_are_its_read_spans(tmp_path):
    st = DatasetStore(str(tmp_path / "st"), "w")
    st.create("d", 40, (3,), "float32")
    data = np.arange(120, dtype=np.float32).reshape(40, 3)
    st.write_rows("d", 0, data)
    t0 = time.perf_counter()
    np.testing.assert_array_equal(st.read_rows("d", 5, 10), data[5:15])
    got = st.read_plan("d", [0, 20], [4, 6])
    np.testing.assert_array_equal(got[1], data[20:26])
    np.testing.assert_array_equal(st.read_rows_at("d", np.array([7, 3, 8])),
                                  data[[7, 3, 8]])
    reads = [s for s in _since(t0) if s.name == "ckpt.load.read"]
    assert [s.attrs["bytes"] for s in reads] == [10 * 12, 10 * 12, 3 * 12]
    assert st.stats.read_seconds == sum(s.seconds for s in reads)
    assert st.stats.bytes_read == 23 * 12
def test_load_jax_one_to_one_records_the_engine_phases(tmp_path):
    state = _state()
    ck = TensorCheckpoint(DatasetStore(str(tmp_path / "ck"), "w"))
    ck.save_layout(layout_from_jax(state))
    save_jax(ck, state, 1)
    target = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=v.sharding)
              for k, v in state.items()}
    t0 = time.perf_counter()
    out = load_jax(ck, target, 1)
    for k in state:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(state[k]))
    rec = _since(t0)
    load, = [s for s in rec if s.name == "ckpt.load"]
    assert load.attrs["bytes"] == sum(int(a.nbytes) for a in state.values())
    top = _names(_children(rec, load))
    assert top == {"ckpt.load.state": 1, "ckpt.load.h2d": 1}
    st, = [s for s in rec if s.name == "ckpt.load.state"]
    kids = _names(_children(rec, st))
    # "w" (16 x 8, one chunk per element) takes the general path: the fast
    # path's test, the block plan and the chunk-size check are plan spans,
    # the chunk-level forests one sf and one bcast span, and the block
    # copies one scatter span; "b" and "n" are one chunk each and read back
    # on the same-count fast path
    assert kids == {"ckpt.load.plan": 3 + 2, "ckpt.load.read": 4 + 2,
                    "ckpt.load.sf": 1, "ckpt.load.bcast": 1,
                    "ckpt.load.scatter": 1 + 2}
    w_chunks = ck.layout().spec("w").grid.num_chunks
    assert w_chunks > 1
    plans = [s.attrs for s in _children(rec, st)
             if s.name == "ckpt.load.plan" and "elements" in s.attrs]
    assert plans == [{"elements": 16 * 8}]
    blocks = [s.attrs for s in _children(rec, st)
              if s.name == "ckpt.load.scatter" and s.attrs]
    assert blocks == [{"blocks": w_chunks, "bytes": 16 * 8 * 4}]


_FOUR_TO_TWO = """
import json, sys
sys.path.insert(0, sys.argv[1])
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import spans as S
from repro.core.jax_io import layout_from_jax, load_jax, save_jax
from repro.core.store import DatasetStore
from repro.core.tensor_ckpt import TensorCheckpoint
from repro.launch.mesh import make_debug_mesh

src = make_debug_mesh(4, 1)
dst = make_debug_mesh(2, 1, devices=jax.devices()[:2])
x = np.arange(64 * 6, dtype=np.float32).reshape(64, 6)
state = {"x": jax.device_put(x, NamedSharding(src, P("data", None)))}
ck = TensorCheckpoint(DatasetStore(sys.argv[2], "w"))
ck.save_layout(layout_from_jax(state))
save_jax(ck, state, 2)
target = {"x": jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(dst, P("data", None)))}
out = load_jax(ck, target, 2)["x"]
assert np.array_equal(np.asarray(out), x)
assert len(out.sharding.device_set) == 2
rec = S.spans()
st = [s for s in rec if s.name == "ckpt.load.state"][-1]
print(json.dumps({
    "load": [s.attrs for s in rec if s.name == "ckpt.load"],
    "children": sorted(s.name for s in rec if s.parent_id == st.span_id),
    "scatter": [s.attrs for s in rec if s.name == "ckpt.load.scatter"
                and s.parent_id == st.span_id],
    "blocks_expected": ck.layout().spec("x").grid.num_chunks,
    "shards": len(out.addressable_shards)}))
"""


def test_load_jax_four_devices_onto_two_records_the_engine_phases(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_TO_TWO,
         str(Path(__file__).resolve().parents[1] / "src"),
         str(tmp_path / "ck")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["load"] == [{"bytes": 64 * 6 * 4}]
    assert got["shards"] == 2
    assert collections.Counter(got["children"]) == {
        "ckpt.load.plan": 3, "ckpt.load.read": 4, "ckpt.load.sf": 1,
        "ckpt.load.bcast": 1, "ckpt.load.scatter": 1}
    # two target boxes of 32 rows, each cut from the saved chunk runs
    assert got["scatter"] == [{"blocks": got["blocks_expected"],
                               "bytes": 64 * 6 * 4}]


# ---------------------------------------------------------- the FE restart
def test_fe_restart_records_the_load_mesh_phases(tmp_path):
    from repro.fem import (Element, FEMCheckpoint, FunctionSpace, distribute,
                           interpolate, tri_mesh)

    mesh = tri_mesh(4, 4, seed=2)
    plexes, _, _ = distribute(mesh, 4)
    ck = FEMCheckpoint(DatasetStore(str(tmp_path / "fe"), "w"))
    ck.save_mesh("m", plexes, Comm(4))
    spaces = [FunctionSpace(lp, Element("P", 2, "triangle"))
              for lp in plexes]
    ck.save_function("m", "f", [interpolate(sp, lambda p: p[:, 0])
                                for sp in spaces], Comm(4))
    t0 = time.perf_counter()
    loaded = ck.load_mesh("m", Comm(2))
    ck.load_function(loaded, "f", Comm(2))
    rec = _since(t0)
    lm, = [s for s in rec if s.name == "fe.load_mesh"]
    assert _names(_children(rec, lm)) == {
        "fe.close": 3, "fe.partition": 2, "fe.owners": 3,
        "fe.build_locals": 1, "fe.directory": 3, "fe.coords": 1}
    assert [s.attrs for s in rec if s.name == "fe.directory"] == [
        {}, {}, {"directories": 2, "queries": 2, "composes": 2}]
    coords, = [s for s in rec if s.name == "fe.coords"]
    assert _names(_children(rec, coords)) == {"fe.load_function": 1}
    top = [s for s in rec if s.name == "fe.load_function"
           and s.parent_id is None]
    assert len(top) == 1 and top[0].t0 >= lm.t1
