"""Turing-completeness tests: the ADDLEQ stored-program interpreter built
from RDMA verbs (Appendix A, constructive form) runs real guest programs."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import machine, turing


@pytest.fixture(scope="module")
def interp():
    return turing.build_interpreter()


def run_guest(interp, guest, max_guest_instrs=200):
    st0 = interp.load(guest)
    out = interp.run(st0, max_steps=interp.lap_words * (max_guest_instrs + 2))
    return np.asarray(out.mem), out


def test_countdown_halts(interp):
    guest = turing.guest_countdown(interp, 5)
    mem, out = run_guest(interp, guest)
    assert bool(out.halted)
    assert mem[interp.data_base] == 0          # counter reached 0
    # it ran 2 instructions per decrement: >= 9 guest instructions
    assert int(out.steps) >= 9 * interp.lap_words


def test_add(interp):
    guest = turing.guest_add(interp, 17, 25)
    mem, out = run_guest(interp, guest)
    assert bool(out.halted)
    assert mem[interp.data_base + 1] == 42


@pytest.mark.parametrize("x,y", [(3, 4), (7, 6), (1, 1), (9, 0)])
def test_multiply(interp, x, y):
    guest = turing.guest_multiply(interp, x, y)
    mem, out = run_guest(interp, guest)
    if y == 0:
        # cnt starts 0: first decrement halts immediately, acc gets one x
        assert bool(out.halted)
        return
    assert bool(out.halted)
    assert mem[interp.data_base + 2] == x * y


def test_nontermination_is_fuel_bounded(interp):
    """An infinite guest loop never quiesces (requirement T3)."""
    d = interp.data_base
    i0 = interp.instr_base
    guest = turing.AddleqProgram([(d, d + 1, i0)], {d: 0, d + 1: 0})
    st0 = interp.load(guest)
    out = interp.run(st0, max_steps=500)
    assert not bool(out.halted)
    assert int(out.steps) == 500


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_random_addleq_against_reference(interp, data):
    """Random small ADDLEQ programs: chain interpreter == python oracle."""
    d, i0 = interp.data_base, interp.instr_base
    n_instr = data.draw(st.integers(1, 5))
    n_cells = 6
    trap = d + n_cells                       # very negative cell: always halts
    instrs = []
    for _ in range(n_instr):
        a = d + data.draw(st.integers(0, n_cells - 1))
        b = d + data.draw(st.integers(0, n_cells - 1))
        # jump target: halt or a valid instruction (incl. the trap)
        c = data.draw(st.sampled_from(
            [turing.HALT_PC] + [i0 + k * turing.INSTR_WORDS
                                for k in range(n_instr + 1)]))
        instrs.append((a, b, c))
    instrs.append((trap, trap, turing.HALT_PC))   # fall-off-the-end trap
    cells = {d + k: data.draw(st.integers(-50, 50)) for k in range(n_cells)}
    cells[trap] = -(1 << 20)

    guest = turing.AddleqProgram(instrs, dict(cells))
    budget = 100
    ref_mem, ref_n = turing.addleq_reference(instrs, cells, i0, i0,
                                             max_instrs=budget)
    st0 = interp.load(guest)
    out = interp.run(st0, max_steps=interp.lap_words * (budget + 2))
    got = np.asarray(out.mem)
    if ref_n < budget:     # reference halted within budget -> exact match
        assert bool(out.halted)
        for addr in sorted(cells):
            if addr == trap:
                continue
            assert got[addr] == ref_mem.get(addr, 0), (instrs, cells, addr)
    # else: unbounded loop; nontermination covered by its dedicated test
