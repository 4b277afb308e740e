"""Swallow core modules: validation against the paper's own numbers plus
property tests (topology routing, striping, scheduler)."""
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (energy, memory_server, network, nos, overlays,
                        ratio, topology)


# --- energy (§VI-VII) -------------------------------------------------------
def test_eqn3_matches_paper():
    # Eqn 3: P = 46 + 0.30 f; paper quotes 193 mW @ 500 MHz, 65 mW @ 71 MHz
    assert abs(energy.swallow_core_power_mw(500) - 196.0) < 1e-6
    assert abs(energy.swallow_core_power_mw(500) - 193.0) < 5.0
    assert abs(energy.swallow_core_power_mw(71) - 67.3) < 3.0


def test_link_energy_table():
    t = energy.SWALLOW_LINK_PJ_PER_BIT
    assert t["on_die"] == 1.63
    # off-board ~50x the on-board energy (paper: "rises by approx 50x")
    assert 40 < t["off_board_ffc"] / t["on_board_h"] < 60


def test_dvfs_saves_over_fs_only():
    # voltage+frequency scaling must beat frequency-only at low f
    p_dvfs = energy.swallow_dvfs_power_mw(71.0)
    p_fs = energy.swallow_core_power_mw(71.0)
    assert p_dvfs < p_fs


def test_step_energy_split():
    e = energy.step_energy(flops_per_chip=1e14, hbm_bytes_per_chip=1e11,
                           ici_bytes_per_chip=1e9, step_seconds=1.0)
    assert abs(sum([e.compute_j, e.hbm_j, e.ici_j, e.static_j])
               - e.total_j) < 1e-9
    assert 0.99 < sum(e.breakdown.values()) < 1.01


# --- ratio (§II-B, Tab. III) -------------------------------------------------
def test_swallow_table_iii():
    r = ratio.swallow_ec()
    assert r.ec == 2.0 and r.EC == 32
    assert r.perf_bound() == 32


def test_cell_ratio_balanced_detection():
    # tiny traffic, big compute -> balanced
    r = ratio.analyze_cell("x", wire_bytes_per_device=1e6,
                           compute_seconds=1.0, n_chips=256,
                           mesh_shape={"data": 16, "model": 16})
    assert r.balanced and r.bound == "compute"
    # huge traffic -> communication bound
    r2 = ratio.analyze_cell("y", wire_bytes_per_device=1e13,
                            compute_seconds=0.1, n_chips=256,
                            mesh_shape={"data": 16, "model": 16})
    assert not r2.balanced


# --- topology (§V-A): the <=2 layer transitions claim ------------------------
@settings(max_examples=60, deadline=None)
@given(rows=st.integers(2, 8), cols=st.integers(2, 8),
       data=st.data())
def test_lattice_routing_properties(rows, cols, data):
    lat = topology.Lattice(rows, cols)
    nodes = list(lat.nodes())
    src = data.draw(st.sampled_from(nodes))
    dst = data.draw(st.sampled_from(nodes))
    path = lat.route(src, dst)
    assert path[0] == src and path[-1] == dst
    # every step is a physical link
    for a, b in zip(path, path[1:]):
        assert b in lat.neighbors(a), (a, b)
    # the paper's claim: at most two layer transitions... plus possibly a
    # final transition when src and dst layers both force crossings
    assert topology.Lattice.layer_transitions(path) <= 3
    # dimension-ordered: vertical moves never follow horizontal moves
    seen_h = False
    for a, b in zip(path, path[1:]):
        if a[0] == b[0] == 1 and a[2] != b[2]:
            seen_h = True
        if a[0] == b[0] == 0 and a[1] != b[1]:
            assert not seen_h


def test_lattice_two_transitions_for_core_routes():
    # the paper's exact case: two nodes on the horizontal layer without a
    # shared vertical index need exactly two transitions
    lat = topology.Lattice(4, 4)
    path = lat.route((1, 0, 0), (1, 3, 3))
    assert topology.Lattice.layer_transitions(path) == 2


def test_lattice_full_connectivity():
    lat = topology.Lattice(3, 3)
    nodes = list(lat.nodes())
    for s in nodes:
        for d in nodes:
            p = lat.route(s, d)
            assert p[0] == s and p[-1] == d


# --- network (§V-B/C) ---------------------------------------------------------
def test_link_rates_match_paper():
    # paper: 500 Mbit/s per internal link at Ts=2, Tt=1, 500 MHz
    assert abs(network.link_rate_bps() - 500e6) / 500e6 < 0.01
    # packetized ~435 Mbit/s effective ("depending on packet size")
    r = network.packet_rate_bps(32)
    assert 420e6 < r < 460e6


def test_circuit_beats_packet_small_messages():
    t_c = network.ring_collective_time(1e4, 16, mode="circuit")
    t_p = network.ring_collective_time(1e4, 16, mode="packet")
    assert t_p > t_c
    # large messages converge
    t_c = network.ring_collective_time(1e9, 16, mode="circuit")
    t_p = network.ring_collective_time(1e9, 16, mode="packet")
    assert (t_p - t_c) / t_c < 0.05


# --- memory server (§III-A / §X-B) --------------------------------------------
@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 64), addr=st.integers(0, 10 ** 6))
def test_striping_rule(n, addr):
    assert memory_server.striped_owner(addr, n) == addr % n


def test_striped_store_roundtrip():
    import jax.numpy as jnp
    st_ = memory_server.StripedStore(size=64)
    addrs = jnp.array([0, 5, 17, 63])
    vals = jnp.array([1.0, 2.0, 3.0, 4.0])
    st_.write(addrs, vals)
    got = st_.read(addrs)
    assert jnp.allclose(got, vals)
    assert float(st_.read(jnp.array([1]))[0]) == 0.0


def test_memory_per_task_fig3():
    # Fig. 3: fixed tasks + growing procs -> exponential memory per task;
    # tasks == procs -> constant 64 kB
    assert memory_server.memory_per_task(1024, 1) == 1024 * 64
    assert memory_server.memory_per_task(1024, 1024) == 64
    assert memory_server.memory_per_task(2048, 1024) == 128


# --- overlays (§III-B) ---------------------------------------------------------
def test_overlay_map_fig4():
    m = overlays.overlay_map()
    assert m["n_overlays"] == 2
    assert m["resident_kwords"] == 12   # paper: 16k -> 12k words


# --- nOS (§VIII) ---------------------------------------------------------------
def test_nos_scheduler():
    s = nos.NOS(data_rows=16)
    assert s.submit(nos.Job("a", rows_needed=8))
    assert s.submit(nos.Job("b", rows_needed=8))
    assert not s.submit(nos.Job("c", rows_needed=4))   # queued
    assert s.jobs["c"].state == "pending"
    assert s.utilisation() == 1.0
    s.finish("a")
    assert s.jobs["c"].state == "running"
    assert s.utilisation() == 0.75


def test_nos_failure_eviction():
    s = nos.NOS(data_rows=8)
    s.submit(nos.Job("a", rows_needed=4))
    evicted = s.fail_rows([0, 1])
    assert "a" in evicted
    # rows 0,1 quarantined; job re-placed on remaining rows
    assert s.jobs["a"].state == "running"
    assert not (set(s.jobs["a"].rows) & {0, 1})


def test_nos_restore_rows_inverts_failure():
    s = nos.NOS(data_rows=8)
    s.submit(nos.Job("a", rows_needed=4))
    s.submit(nos.Job("b", rows_needed=4))
    s.fail_rows([0, 1, 2, 3])
    # half the pod is dark: only one job fits the surviving rows
    states = sorted(j.state for j in s.jobs.values())
    assert states == ["pending", "running"]
    placed = s.restore_rows([0, 1, 2, 3])
    # recovery re-admits the stranded job onto the recovered capacity
    assert len(placed) == 1
    assert all(j.state == "running" for j in s.jobs.values())
    assert s._quarantined == set()
    used = [r for j in s.jobs.values() for r in j.rows]
    assert len(used) == len(set(used)) == 8


def test_nos_restore_rows_ignores_healthy_rows():
    s = nos.NOS(data_rows=8)
    s.submit(nos.Job("a", rows_needed=4))       # holds rows 0-3
    # restoring rows a running job holds must not double-free them
    assert s.restore_rows([0, 1]) == []
    assert sorted(s._free) == [4, 5, 6, 7]
    assert s.jobs["a"].state == "running"
    s.fail_rows([5])                            # idle row: nothing evicted
    assert s.restore_rows([5, 6, 7]) == []      # 6,7 never quarantined
    assert 5 in s._free and s._quarantined == set()
    assert sorted(s._free) == [4, 5, 6, 7]
    assert s.jobs["a"].rows == (0, 1, 2, 3)


@settings(max_examples=20, deadline=None)
@given(rows=st.integers(2, 32),
       sizes=st.lists(st.integers(1, 8), min_size=1, max_size=10))
def test_nos_never_overlaps(rows, sizes):
    s = nos.NOS(data_rows=rows)
    for i, n in enumerate(sizes):
        s.submit(nos.Job(f"j{i}", rows_needed=n))
    used = []
    for j in s.jobs.values():
        if j.state == "running":
            used.extend(j.rows)
    assert len(used) == len(set(used))          # no double allocation
    assert all(0 <= r < rows for r in used)
