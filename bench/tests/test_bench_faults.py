"""A run with the timed path broken underneath must come out not correct.

Each case drives the whole harness of a cell at smoke size on the CPU (the
look for a chip skipped), judged by the numbers of the cell's own check
file, with one fault planted in the engine's decode step — the faults a
served cell can have on one chip — and checks that the run's ``correct``
reads false while a sound run reads true."""
import dataclasses

import jax.numpy as jnp
import pytest

import run
from smoke_cell import CELLS, smoke_cell, smoke_limits

SEED = 2 ** 31 + 99


def _wrap_decode(eng, change, donate=True):
    ex = eng.executor
    if not donate:
        ex.exec_cfg = dataclasses.replace(ex.exec_cfg, donate_state=False)
    orig = ex.decode
    calls = {"n": 0}

    def decode(sp, state, pa, tokens, active=None, rows=None):
        calls["n"] += 1
        return change(orig, calls["n"], sp, state, pa, tokens, active, rows)

    ex.decode = decode


def state_unchanged(eng):
    """The step hands back the state it was given."""
    def change(orig, n, sp, state, pa, tokens, active, rows):
        _, lg = orig(sp, state, pa, tokens, active, rows)
        return state, lg
    _wrap_decode(eng, change, donate=False)


def half_batch_left_out(eng):
    """Odd rows are left out of every decode step."""
    def change(orig, n, sp, state, pa, tokens, active, rows):
        if active is not None:
            active = active & (jnp.arange(active.shape[0]) % 2 == 0)
        return orig(sp, state, pa, tokens, active, rows)
    _wrap_decode(eng, change)


def token_altered(eng):
    """Every fifth decode step emits the next token id instead."""
    def change(orig, n, sp, state, pa, tokens, active, rows):
        st, lg = orig(sp, state, pa, tokens, active, rows)
        if n % 5 == 0:
            st = dataclasses.replace(st, last_tokens=(st.last_tokens + 1) % 256)
        return st, lg
    _wrap_decode(eng, change)


def _run(workload, fault=None):
    return run.run(workload, SEED, 2.0, False, require_chip=False,
                   limits=smoke_limits(workload), fault=fault,
                   cell=smoke_cell(*CELLS[workload]))


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(workload):
    res = _run(workload)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", sorted(CELLS))
@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered])
def test_fault_is_caught(workload, fault):
    res = _run(workload, fault)
    assert not res["correct"], (fault.__name__, res["checks"])
