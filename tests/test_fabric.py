"""The ``repro.fabric`` unified topology API.

Covers the instance registry (including the ``mirror`` instance that is
registered *only* through ``register_instance`` — the proof that no
dispatch edits are needed), the uniform ``Fabric`` surface over
CIN / HyperX / Dragonfly, the closed-form Dragonfly link loads against
the packet simulator's routed ground truth, and the deprecation shims.
"""
import numpy as np
import pytest

from repro import fabric
from repro.core import (DragonflyConfig, HyperXConfig, dragonfly_link_loads,
                        port_matrix)
from repro.sim.topology import dragonfly_topology, routed_link_loads


# ---------------------------------------------------------------------------
# Registry + mirror.
# ---------------------------------------------------------------------------

def test_builtins_and_mirror_registered():
    names = fabric.instance_names()
    assert set(names) >= {"swap", "circle", "xor", "mirror"}
    assert set(fabric.instance_names(isoport=True)) >= {"circle", "xor",
                                                        "mirror"}
    assert "swap" not in fabric.instance_names(isoport=True)


def test_mirror_is_a_distinct_matrix_with_the_same_factors():
    """mirror = Circle with reversed port colours: same 1-factor *set*,
    different P matrix (different colour per wire)."""
    for n in (8, 9, 16):
        Pm = port_matrix("mirror", n)
        Pc = port_matrix("circle", n)
        assert not np.array_equal(Pm, Pc)
        cols = Pm.shape[1]
        for i in range(cols):
            assert np.array_equal(Pm[:, i], Pc[:, (-i) % cols])


def test_registered_instance_reaches_every_layer():
    """mirror flows through matrix, routing, schedule, sim and Fabric —
    none of which mention it."""
    from repro.core import make_schedule, route, verify_instance
    from repro.sim.topology import cin_topology
    assert verify_instance("mirror", 12)["ok"]
    assert int(route("mirror", 3, 7, 12)) >= 0
    s = make_schedule("mirror", 12)
    assert s.is_matching_per_step() and s.covers_all_pairs()
    cin_topology("mirror", 12).validate()
    assert fabric.make_fabric("mirror", 12).verify()["ok"]


def test_register_and_unregister_custom_instance():
    """A throwaway instance registered at test time is fully usable."""
    # 'cyclic-pairing' on even n: partner = (i+1-s) mod n is an involution
    # iff ... use a relabelled xor to keep it simple and valid.
    fabric.register_instance(
        "xor_relabel",
        neighbor=lambda s, i, n: (s ^ (i + 1)),
        route=lambda a, b, n: (a ^ b) - 1,
        constraints=lambda n: fabric.get_instance("xor").check(n))
    try:
        rep = fabric.make_fabric("xor_relabel", 8).verify()
        assert rep["ok"] and rep["isoport"]
    finally:
        fabric.unregister_instance("xor_relabel")
    with pytest.raises(ValueError):
        fabric.get_instance("xor_relabel")


# ---------------------------------------------------------------------------
# The uniform Fabric surface.
# ---------------------------------------------------------------------------

FABRICS = [
    fabric.make_fabric("xor", 8),
    fabric.make_fabric("circle", 9),
    fabric.make_fabric("mirror", 8),
    fabric.make_fabric("swap", 8),
    fabric.make_fabric(HyperXConfig(dims=(4, 4), terminals=4)),
    fabric.make_fabric(DragonflyConfig(4, 2, 1, 5)),
]


@pytest.mark.parametrize("fab", FABRICS, ids=lambda f: f.name)
def test_fabric_uniform_surface(fab):
    assert fab.verify()["ok"], fab.name
    topo = fab.sim_topology()
    topo.validate()
    assert topo.num_switches == fab.num_switches
    assert fab.num_links == topo.num_links
    nb = fab.neighbor_matrix()
    pp = fab.peer_port_matrix()
    assert nb.shape == pp.shape == (topo.num_switches, topo.num_ports)
    assert isinstance(fab.link_loads(), dict)
    dep = fab.deployment()
    assert isinstance(dep, dict) and dep
    assert fab.diameter >= 1
    assert fab.schedule() is not None


def test_cin_fabric_uniform_loads():
    ll = fabric.make_fabric("xor", 16).link_loads()
    assert set(ll["per_link"].values()) == {1}
    assert ll["summary"]["links_used"] == 16 * 15


def test_hyperx_fabric_balanced_loads_and_deployment():
    fab = fabric.make_fabric(HyperXConfig(dims=(4, 4), terminals=4))
    assert fab.link_loads()["load_cv"] == 0.0
    assert fab.deployment()["switches"] == 16


def test_make_fabric_dispatch_errors():
    with pytest.raises(ValueError):
        fabric.make_fabric("xor")          # missing n
    with pytest.raises(TypeError):
        fabric.make_fabric(3.14)
    f = fabric.make_fabric("xor", 8)
    assert fabric.make_fabric(f) is f      # pass-through


# ---------------------------------------------------------------------------
# Dragonfly closed-form loads vs the packet simulator, link for link.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [
    DragonflyConfig(4, 2, 1, 5),
    DragonflyConfig(8, 4, 2, 16),
    DragonflyConfig(4, 2, 1, 5, local_instance="mirror",
                    global_instance="mirror"),
    DragonflyConfig(4, 2, 2, 8, local_instance="xor", global_instance="xor"),
    DragonflyConfig(4, 2, 2, 9, local_instance="swap",
                    global_instance="circle"),
], ids=lambda c: f"a{c.group_size}g{c.num_groups}-{c.local_instance}-"
                 f"{c.global_instance}")
def test_dragonfly_closed_form_matches_routed_ground_truth(cfg):
    """Every directed physical link: closed form == hop-by-hop routing."""
    cf = dragonfly_link_loads(cfg)
    routed = routed_link_loads(dragonfly_topology(cfg))
    a = cfg.group_size
    want: dict[tuple[int, int], int] = {}
    for (grp, s, t), v in cf["local"].items():
        key = (grp * a + s, grp * a + t)
        want[key] = want.get(key, 0) + v
    for (ga, gb), v in cf["global"].items():
        sa, _ = cfg.global_port_owner(ga, gb)
        sb, _ = cfg.global_port_owner(gb, ga)
        key = (ga * a + sa, gb * a + sb)
        want[key] = want.get(key, 0) + v
    assert want == routed


def test_dragonfly_global_links_perfectly_balanced():
    cfg = DragonflyConfig(8, 4, 2, 16)
    cf = dragonfly_link_loads(cfg)
    assert set(cf["global"].values()) == {64}      # a^2
    assert cf["summary"]["global_link_load"] == 64
    assert cf["summary"]["global_links_used"] == 16 * 15


# ---------------------------------------------------------------------------
# Mesh shape checking (the axis_size foot-gun, now a loud error).
# ---------------------------------------------------------------------------

def test_collectives_mesh_shape_check():
    jax = pytest.importorskip("jax")
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs.reshape(1), ("x",))
    fab = fabric.make_fabric("xor", 8)
    with pytest.raises(ValueError, match="needs 8"):
        fab.collectives(mesh, axis_name="x")
    # HyperX checks every dimension and the axis count.
    hfab = fabric.make_fabric(HyperXConfig(dims=(4, 4), terminals=4))
    with pytest.raises(ValueError, match="dimensions"):
        hfab.collectives(mesh, axis_names=("x",))
    # Dragonfly checks local and global axes independently.
    dfab = fabric.make_fabric(DragonflyConfig(4, 2, 1, 5))
    with pytest.raises(ValueError, match="local CIN"):
        dfab.collectives(mesh, local_axis="x")


def test_collectives_instance_binding():
    fab = fabric.make_fabric(DragonflyConfig(
        4, 2, 1, 5, local_instance="circle", global_instance="mirror"))
    coll = fab.collectives(None, local_axis="l", global_axis="g")
    assert coll.axis_instance("l") == "circle"
    assert coll.axis_instance("g") == "mirror"
    assert coll.axis_instance("other") == "auto"


# ---------------------------------------------------------------------------
# Deprecation shims: old entry points warn but still work.
# ---------------------------------------------------------------------------

def test_instances_tuple_is_deprecated():
    import importlib

    import repro.core
    # (the package re-exports the port_matrix *function* under the same
    # name, so fetch the module object itself)
    pm = importlib.import_module("repro.core.port_matrix")
    with pytest.warns(fabric.LacinDeprecationWarning):
        assert pm.INSTANCES == ("swap", "circle", "xor")
    with pytest.warns(fabric.LacinDeprecationWarning):
        assert repro.core.INSTANCES == ("swap", "circle", "xor")


def test_collective_shims_warn():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.core import psum_or_lacin, tree_all_reduce_lacin

    # The warnings fire at call time, before any collective is traced:
    # an empty pytree exercises the tree shim with no bound axis needed,
    # and the xla psum path runs inside a trivial size-1 shard_map.
    with pytest.warns(fabric.LacinDeprecationWarning):
        assert tree_all_reduce_lacin({}, "x", axis_size=4) == {}

    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

    def body(x):
        with pytest.warns(fabric.LacinDeprecationWarning):
            return psum_or_lacin(x, "x", axis_size=1, impl="xla")

    out = shard_map(body, mesh=mesh, in_specs=P(), out_specs=P())(
        jnp.ones((4,)))
    assert out.shape == (4,)
