"""The scheduler's observation row: golden column values and order for the
live cluster's rows and the simulator's ``observe`` / ``state_scale``."""
import itertools
import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import EdgeCluster, Request, make_scheduler
from repro.core import env as envlib
from repro.core.env import EnvParams
from repro.core.obs import ObsLayout
from repro.faults import FaultParams
from repro.workload import QoSClass


def _stub(pending, token_s, arch, availability, hit):
    """The engine attributes ``EdgeCluster.observe`` reads, and no more."""
    return types.SimpleNamespace(
        pending_tokens=pending, est_token_seconds=token_s, arch_id=arch,
        availability=availability, available=availability > 0,
        expected_prefix_tokens=lambda req: hit)


def _live_row(scheduler):
    engines = [_stub(100, 0.05, "qwen2-1.5b", 1.0, 48),
               _stub(20, 0.125, "xlstm-350m", 0.5, 0)]
    cluster = EdgeCluster(engines, scheduler, clock=lambda: 10.0)
    req = Request(rid=0, prompt=np.zeros((1, 40), np.int32),
                  max_new_tokens=24, arrival_s=1.0, deadline_s=3.5,
                  model_pref="xlstm-350m")
    req.t_arrival = 9.0                 # 1 s of the 2.5 s budget spent
    row = np.asarray(cluster.observe(req))
    assert row.shape == (cluster.obs_dim,) and row.dtype == np.float32
    return row


def test_live_base_row_golden():
    """The chat cells' row: ``[d, w, q_1, q_2]`` over LiveObsConfig's
    scales; slack, affinity, availability and prefix hits stay out."""
    np.testing.assert_array_equal(
        _live_row(make_scheduler("jsq", 2)),
        np.asarray([1.25, 1.5, 1.5625, 0.3125], np.float32))


def test_live_qos_fault_prefix_row_golden():
    """Every block, in the one order: base, slack + affinity (engine 0
    is off the preferred arch, so its affinity is inflated 4x), then
    availability, then expected prefix hits."""
    np.testing.assert_array_equal(
        _live_row(make_scheduler("prefix-affinity", 2, qos=True,
                                 fault=True)),
        np.asarray([1.25, 1.5, 1.5625, 0.3125,
                    0.375, 4.8, 3.0,
                    1.0, 0.5,
                    1.5, 0.0], np.float32))


_QOS_FAULT = EnvParams(
    num_bs=2, max_tasks=10,
    qos_mix=((QoSClass("fast", priority=4.0, deadline_s=2.0,
                       z_range=(1, 20)), 0.5),
             (QoSClass("bulk", z_range=(4, 8)), 0.5)),
    fault=FaultParams())


def test_env_observe_golden_qos_fault():
    p = _QOS_FAULT
    qs = envlib.QueueState(q_prev=jnp.asarray([1.0, 3.0]),
                           q_bef=jnp.zeros(2))
    s = envlib.observe(p, qs, jnp.asarray([2.0, 4.0]),
                       jnp.asarray([0.5, 1.5]),
                       slack=jnp.asarray([0.25, jnp.inf]),
                       f=jnp.asarray([10.0, 40.0]),
                       avail=jnp.asarray([1.0, 0.0]))
    np.testing.assert_allclose(
        np.asarray(s),
        #  d    w    q_1  q_2  slack  c_1    c_2      a_1  a_2
        [[2.0, 0.5, 1.0, 3.0, 0.25, 0.05, 0.0125, 1.0, 0.0],
         [4.0, 1.5, 1.0, 3.0, 10.0, 0.15, 0.0375, 1.0, 0.0]],
        rtol=1e-7)


def test_env_state_scale_golden_qos_fault():
    np.testing.assert_allclose(
        np.asarray(envlib.state_scale(_QOS_FAULT)),
        #  d_hi  w_hi  q_hi (x2)     slack  c (x2)      a (x2)
        [5.0, 6.0, 60.0, 60.0, 10.0, 0.6, 0.6, 1.0, 1.0],
        rtol=1e-6)


_LAYOUTS = [ObsLayout(3, *flags)
            for flags in itertools.product((False, True), repeat=3)]


def _columns(layout):
    """Column names in the documented row order, built independently."""
    E = range(layout.num_engines)
    cols = ["d", "w"] + [f"q{e}" for e in E]
    if layout.qos:
        cols += ["slack"] + [f"c{e}" for e in E]
    if layout.fault:
        cols += [f"a{e}" for e in E]
    if layout.prefix:
        cols += [f"hit{e}" for e in E]
    return cols


@pytest.mark.parametrize("row", _LAYOUTS, ids=str)
def test_layout_reads_over_the_eight_layouts(row):
    """A row is readable as layout L exactly when L's columns are the
    row's leading columns: the base row reads everything, and a
    fault-only consumer cannot read a QoS + fault row."""
    cols = _columns(row)
    assert row.dim == len(cols)
    for name, sl in row.slices.items():
        assert all(c.startswith({"aff": "c", "avail": "a"}.get(name, name))
                   for c in cols[sl])
    for consumer in _LAYOUTS:
        want = cols[:consumer.dim] == _columns(consumer)
        assert row.reads(consumer) == want, consumer
    assert row.reads(ObsLayout(3))
    assert not ObsLayout(3, qos=True, fault=True).reads(
        ObsLayout(3, fault=True))


def test_layout_assemble_refuses_missing_extra_or_misshapen_blocks():
    layout = ObsLayout(2, fault=True)
    one, two = np.zeros(1, np.float32), np.zeros(2, np.float32)
    row = layout.assemble(d=one, w=one + 1, q=two + 2, avail=two + 3)
    np.testing.assert_array_equal(row, [0, 1, 2, 2, 3, 3])
    assert isinstance(row, np.ndarray)
    with pytest.raises(ValueError, match="avail"):
        layout.assemble(d=one, w=one, q=two)
    with pytest.raises(ValueError, match="hit"):
        layout.assemble(d=one, w=one, q=two, avail=two, hit=two)
    with pytest.raises(ValueError, match="'q' is 1 wide"):
        layout.assemble(d=one, w=one, q=one, avail=two)
