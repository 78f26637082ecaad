"""``correct`` on the CPU at a test's size: sound runs pass; the control
(the plain reference in the next precision below the configuration's, in
the program's place) and each fault planted in the timed path fail."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import tiny_cells
from benchmarks.chip import calibrate

TRAIN = "smollm-135m.train_save"
RESTORE = "smollm-135m.restore"
FE = "fe-p4-tri.restart_4to2"
FOUR = {"name": "smollm-135m.reshard_4x1_to_2x2", "config": "smollm-135m",
        "traffic": "reshard_4x1_to_2x2", "chips": 4}


@pytest.mark.parametrize("name", [TRAIN, RESTORE, FE])
def test_sound_run_is_correct(name):
    out = tiny_cells.run_tiny(name)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1
    assert list(out)[-1] == "checks"


def _limits(name):
    return tiny_cells.tiny_cell(name).config["limits"]["train"]


def test_train_control_fails_a_limit():
    rows = []
    calibrate.calibrate_train(tiny_cells.tiny_cell(TRAIN), [], [3],
                              jax.devices(), rows.append)
    control = next(r for r in rows if r["kind"] == "control_fp8")
    limits = _limits(TRAIN)
    assert any(control[k] > limits[k] for k in limits), control


def test_restore_control_fails():
    rows = []
    calibrate.calibrate_restore(tiny_cells.tiny_cell(RESTORE), [3],
                                jax.devices(), rows.append)
    assert rows[0]["leaves_differ"] > 0


def test_fe_control_fails():
    rows = []
    calibrate.calibrate_fe(tiny_cells.tiny_cell(FE), [3], jax.devices(),
                           rows.append)
    assert rows[0]["dofs_differ"] > 0


# ----------------------------------------------------- faults in the program
def _state_unchanged(monkeypatch):
    from repro.train.optim import AdamW

    monkeypatch.setattr(AdamW, "update",
                        lambda self, params, grads, state, lr, step:
                        (params, state))


def _half_batch(monkeypatch):
    import repro.models.transformer as tf

    loss = tf.loss_fn

    def half(params, cfg, batch):
        mask = batch["mask"].at[batch["mask"].shape[0] // 2:].set(0.0)
        return loss(params, cfg, {**batch, "mask": mask})

    monkeypatch.setattr(tf, "loss_fn", half)


def _saved_byte_altered(monkeypatch):
    from repro.core.store import DatasetStore

    write_plan = DatasetStore.write_plan

    def altered(self, name, starts, arrays):
        if name.endswith("/vec") and "/s3/" not in name:
            arrays = [np.array(a) for a in arrays]
            arrays[0].view(np.uint8).reshape(-1)[0] ^= 1
        return write_plan(self, name, starts, arrays)

    monkeypatch.setattr(DatasetStore, "write_plan", altered)


def _restored_value_altered(monkeypatch):
    from repro.core.tensor_ckpt import TensorCheckpoint

    load_state = TensorCheckpoint.load_state

    def altered(self, *args, **kwargs):
        out = load_state(self, *args, **kwargs)
        for arrs in out[0].values():
            arrs[0].reshape(-1).view(np.uint8)[-1] ^= 1
        return out

    monkeypatch.setattr(TensorCheckpoint, "load_state", altered)


def _fe_value_altered(monkeypatch):
    from repro.fem.checkpoint import FEMCheckpoint

    load_function = FEMCheckpoint.load_function

    def altered(self, *args, **kwargs):
        spaces, funcs = load_function(self, *args, **kwargs)
        funcs[-1].values[0] = np.nextafter(funcs[-1].values[0], 3.0)
        return spaces, funcs

    monkeypatch.setattr(FEMCheckpoint, "load_function", altered)


@pytest.mark.parametrize("name,plant,fails", [
    (TRAIN, _state_unchanged, "change_norm_gap"),
    (TRAIN, _half_batch, "grad_norm_gap"),
    (TRAIN, _saved_byte_altered, "saved_leaves_differ"),
    (RESTORE, _restored_value_altered, "leaves_differ"),
    (FE, _fe_value_altered, "dofs_differ"),
])
def test_fault_in_the_timed_path_is_not_correct(name, plant, fails,
                                                monkeypatch):
    plant(monkeypatch)
    out = tiny_cells.run_tiny(name)
    assert out["correct"] is False
    check = out["checks"][fails]
    assert check["value"] > check["limit"], out["checks"]


_FOUR_DEVICES = """
import json, sys
sys.path.insert(0, sys.argv[1])
import jax
import tiny_cells

def no_exchange(make):
    # every device gets the first shard: the placement across chips is left out
    def make_array(shape, sharding, cb, *args, **kwargs):
        first = []
        def only_first(index):
            if not first:
                first.append(cb(index))
            return first[0]
        return make(shape, sharding, only_first, *args, **kwargs)
    return make_array

cell = tiny_cells.tiny_cell(sys.argv[2], one_device=False,
                            workload=json.loads(sys.argv[3]))
out = [tiny_cells.run_tiny(cell.name, cell=cell)]
jax.make_array_from_callback = no_exchange(jax.make_array_from_callback)
out.append(tiny_cells.run_tiny(cell.name, cell=cell))
print(json.dumps([{"correct": o["correct"], "checks": o["checks"]}
                  for o in out]))
"""


def test_four_chip_reshard_fails_without_the_exchange():
    """The (4, 1) -> (2, 2) cell on four virtual CPU devices: sound, then
    with each device's shard replaced by the first device's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES,
         str(Path(__file__).resolve().parent), FOUR["name"],
         json.dumps(FOUR)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    sound, broken = json.loads(proc.stdout.strip().splitlines()[-1])
    assert sound["correct"] is True, sound["checks"]
    assert broken["correct"] is False
    assert broken["checks"]["leaves_differ"]["value"] > 0
