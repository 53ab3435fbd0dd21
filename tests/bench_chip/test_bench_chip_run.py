"""A whole run of each configuration on the CPU at a size a test can hold,
with the harness's look for a chip skipped: a sound run is correct, and a
run whose timed path is broken underneath the harness is not: a step that
returns its state unchanged, half of a batch left out, an answer altered
where it is produced, and for a stack of frames, all but the last frame
left out or a frame before the last altered where the stack is made. (Every cell runs on one chip: there is no exchange
between chips to leave out.) The lower-precision control fails the same
limits the run is held to."""
import copy
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip.harness import passes, read_metrics, run_cell  # noqa: E402
from bench_cells import CELLS, cell_loader  # noqa: E402,F401  (a fixture)

SECONDS = 1.5


def tiny(cell):
    """The cell with its sizes cut for the CPU, at a rate at which batches
    hold several messages; the checks' limits are the configuration's own."""

    cfg = copy.deepcopy(cell.config)
    if cfg["kind"] == "tomo":
        cfg["sizes"].update(n_angles=16, n_det=64, pool=4, ellipses=3)
        cfg["stage"]["options"]["n"] = 64
        cfg["broker"]["transport_options"]["slot_bytes"] = 2 * 16 * 64 * 4 + 4096
        cell.traffic = {"rate_msgs_s": 30.0}
    else:
        cfg["sizes"].update(points_per_msg=500, pool=16)
        cfg["check"]["min_batches"] = 3
        cell.traffic = {"rate_msgs_s": 60.0}
    cell.config = cfg
    return cell


def run(cell, fault=None, control=False, seed=2**31 + 99):
    return run_cell(cell, seed, SECONDS, False, t_process=time.monotonic(),
                    require_tpu=False, compile_cache=False, fault=fault, control=control)


def unchanged_state(app):
    inner = app.process

    def process(state, msgs):
        inner(state, msgs)
        return state
    app.process = process


def half_batch(app):
    inner = app.process
    app.process = lambda state, msgs: inner(state, msgs[: max(1, len(msgs) // 2)])


def altered_answer(app):
    inner = app.process

    def process(state, msgs):
        out = inner(state, msgs)
        return out.at[0, 0].add(1.0 + abs(out).max())
    app.process = process


def last_frame_only(app):
    inner = app.process
    app.process = lambda state, msgs: inner(state, msgs[-1:])


def stack_frame_altered(app):
    """Row 0 of every stack altered inside the stack's program, below the
    benchmark's tap: the state (the last row) stays right."""
    tapped = app._rec_batch
    program = tapped.inner

    def altered(stack, angles):
        out = program(stack, angles)
        return out.at[0].add(1.0 + abs(out[0]).max())
    tapped.inner = altered




@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct_and_control_is_not(cell_name, cell_loader):
    cell = tiny(cell_loader(cell_name))
    out = run(cell, control=True)
    assert out.correct, out.checks
    assert out.checks["failed_msgs"]["value"] == 0
    assert max(len(b.messages) for b in out.record.batches) > 1
    metrics = read_metrics(cell, out.record, False)
    assert {m["name"] for m in cell.end_to_end} == set(metrics)
    assert not all(passes(c) for c in out.control_checks.values()), out.control_checks


@pytest.mark.parametrize("fault", [unchanged_state, half_batch, altered_answer],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell_name", CELLS)
def test_broken_timed_path_is_not_correct(cell_name, fault, cell_loader):
    out = run(tiny(cell_loader(cell_name)), fault=fault)
    assert not out.correct, out.checks


@pytest.mark.parametrize("fault", [last_frame_only, stack_frame_altered],
                         ids=lambda f: f.__name__)
def test_a_stack_is_checked_frame_by_frame(fault, cell_loader):
    cell = tiny(cell_loader("aps-gridrec.steady"))
    sound = run(cell)
    assert sound.correct, sound.checks
    out = run(cell, fault=fault)
    assert any(len(b.messages) > 1 for b in out.record.batches)
    assert not out.correct, out.checks
