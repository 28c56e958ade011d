"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a cell at a test size, through its real
driver, with one fault planted in the program: a step that returns its
state unchanged, half of the batch left out, the exchange between chips
left out, or an answer altered where it is produced.  The open-loop
cells are held here to test-size limits (a small fabric agrees with the
reference less closely); the replay and the collective keep theirs.
"""
import numpy as np
import pytest

from cells import run, tiny

#: What a 6x4 HyperX with 3 terminals or a 36-switch Dragonfly reads
#: against the reference, with room (the full-size limits are in
#: bench/limits).
SMALL = {"accepted": 0.05, "latency": 0.15, "links": 0.05}


@pytest.fixture
def xengine():
    from repro.sim import xengine
    return xengine


SIM_CELLS = ["df2064.uniform.minimal", "hx12x8.uniform.adaptive",
             "hx12x8.a2a.replay", "df2064.adversarial.adaptive"]
OPEN_LOOP = [c for c in SIM_CELLS if c != "hx12x8.a2a.replay"]


def sim(cell):
    return tiny(cell, **(SMALL if cell in OPEN_LOOP else {}))


def correct(cell) -> bool:
    """A run's ``correct``; a run that raises (a replay that never
    drains) is not correct either."""
    try:
        return run(sim(cell))["correct"]
    except RuntimeError:
        return False


@pytest.mark.parametrize("cell", SIM_CELLS + ["lacin.allreduce.4chip"])
def test_sound_runs_are_correct(cell):
    assert correct(cell)


@pytest.mark.parametrize("cell", SIM_CELLS)
def test_step_returning_its_state(xengine, monkeypatch, cell):
    monkeypatch.setattr(xengine, "_step", lambda spec, tables, pkt, key,
                        warmup, st: st._replace(cycle=st.cycle + 1))
    assert not correct(cell)


@pytest.mark.parametrize("cell", OPEN_LOOP)
def test_half_the_batch_left_out(xengine, monkeypatch, cell):
    sweep = xengine.sweep

    def half(topo, policy, factory, loads, **kw):
        kept = list(loads)[:max(len(loads) // 2, 1)]
        rows = sweep(topo, policy, factory, kept, **kw)
        return [rows[i % len(rows)] for i in range(len(loads))]

    monkeypatch.setattr(xengine, "sweep", half)
    assert not correct(cell)


@pytest.mark.parametrize("cell", SIM_CELLS)
def test_answer_altered_where_produced(xengine, monkeypatch, cell):
    build = xengine.build_stats

    def late(**kw):
        kw["deliver"] = np.where(kw["deliver"] >= 0, kw["deliver"] + 1, -1)
        return build(**kw)

    monkeypatch.setattr(xengine, "build_stats", late)
    assert not correct(cell)


def _collective_fault(monkeypatch, fn):
    from repro.fabric import LacinCollectives
    monkeypatch.setattr(LacinCollectives, "all_reduce", fn)
    return run(tiny("lacin.allreduce.4chip"))["correct"]


def test_collective_state_unchanged(monkeypatch):
    assert not _collective_fault(monkeypatch, lambda self, x, axis: x)


def test_collective_exchange_left_out(monkeypatch):
    """Reduce-scatter without the all-gather: each chip keeps only its
    own reduced shard and its own data elsewhere."""
    from repro.core.collectives import reduce_scatter_lacin

    def no_gather(self, x, axis):
        n = self.axis_size(axis)
        chunks = x.reshape(n, -1)
        mine = reduce_scatter_lacin(chunks, axis, axis_size=n,
                                    instance=self.axis_instance(axis))
        import jax
        i = jax.lax.axis_index(axis)
        return chunks.at[i].set(mine).reshape(x.shape)

    assert not _collective_fault(monkeypatch, no_gather)


def test_collective_half_the_bucket_left_out(monkeypatch):
    from repro.core.collectives import all_reduce_lacin

    def half(self, x, axis):
        h = x.shape[0] // 2
        done = all_reduce_lacin(x[:h], axis, axis_size=self.axis_size(axis),
                                instance=self.axis_instance(axis))
        return x.at[:h].set(done)

    assert not _collective_fault(monkeypatch, half)


def test_collective_answer_altered(monkeypatch):
    from repro.core.collectives import all_reduce_lacin

    def altered(self, x, axis):
        out = all_reduce_lacin(x, axis, axis_size=self.axis_size(axis),
                               instance=self.axis_instance(axis))
        return out.at[0].add(1.0)

    assert not _collective_fault(monkeypatch, altered)
