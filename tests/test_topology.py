"""DeviceTopology / mesh tests: mesh axis order matches placement strategy,
Ranker and mesh agree on device placement, smp.init wiring."""

import numpy as np
import pytest

import jax

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.backend.config import ModelParallelConfig
from smdistributed_modelparallel_tpu.backend.topology import DeviceTopology
from smdistributed_modelparallel_tpu.utils.exceptions import DeviceCountError


def test_mesh_axis_order_cluster():
    cfg = ModelParallelConfig(
        {"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2, "ddp": True}
    )
    topo = DeviceTopology(cfg)
    # cluster == DPT: D-block (rdp, ep, cp) first, then pp, then tp.
    assert topo.axis_names == ("rdp", "ep", "cp", "pp", "tp")
    assert topo.mesh.shape["pp"] == 2
    assert topo.mesh.shape["tp"] == 2
    assert topo.mesh.shape["rdp"] == 2


def test_mesh_axis_order_spread():
    cfg = ModelParallelConfig(
        {"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2, "ddp": True,
         "placement_strategy": "spread"}
    )
    topo = DeviceTopology(cfg)
    # spread == TPD
    assert topo.axis_names == ("tp", "pp", "rdp", "ep", "cp")


def test_mesh_matches_ranker():
    cfg = ModelParallelConfig(
        {"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2, "ddp": True}
    )
    topo = DeviceTopology(cfg)
    devices = list(jax.devices())
    flat_mesh = list(topo.mesh.devices.flat)
    # Mesh is laid out in placement order, so flat index == global rank and
    # the ranker's grid must match device ids.
    for rank in range(topo.size):
        assert flat_mesh[rank] == devices[rank]
        coords = topo.coords(rank)
        assert coords["pp"] == topo.ranker.get_pp_rank(rank)
        assert coords["tp"] == topo.ranker.get_tp_rank(rank)
        assert coords["rdp"] == topo.ranker.get_rdp_rank(rank)


def test_device_count_validation():
    cfg = ModelParallelConfig({"pipeline_parallel_degree": 3, "microbatches": 3})
    with pytest.raises(DeviceCountError):
        DeviceTopology(cfg)


def test_device_count_override():
    cfg = ModelParallelConfig(
        {"pipeline_parallel_degree": 2, "_device_count_override": 4}
    )
    topo = DeviceTopology(cfg, devices=list(jax.devices()))
    assert topo.size == 4
    assert topo.rdp_size == 2


def test_cp_carved_from_dp():
    cfg = ModelParallelConfig({"context_parallel_degree": 2, "ddp": True})
    topo = DeviceTopology(cfg)
    assert topo.cp_size == 2
    assert topo.rdp_size == 4
    assert topo.d_size == 8  # reference "D" dim includes cp/ep
    for rank in range(8):
        assert topo.coords(rank)["cp"] in (0, 1)


def test_smp_init_api():
    smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2, "ddp": True})
    assert smp.is_initialized()
    assert smp.size() == 8
    assert smp.pp_size() == 2
    assert smp.tp_size() == 2
    assert smp.rdp_size() == 2
    assert smp.dp_size() == 4
    assert smp.mp_size() == 4
    assert smp.rank() == 0
    assert sorted(smp.get_world_group()) == list(range(8))
    assert smp.get_mesh().shape["pp"] == 2
    assert len(smp.get_pp_group()) == 2
    assert len(smp.get_dp_group()) == 4


def test_collective_communicator_single_process():
    smp.init({})
    comm = smp.CollectiveCommunicator()
    assert comm.broadcast({"a": 1}) == {"a": 1}
    assert comm.allgather([1, 2]) == [[1, 2]]


def test_axis_group_cp():
    """axis_group returns the devices varying only along the given axis
    (backs CommGroup.CP_GROUP resolution in backend/collectives.py)."""
    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.backend.state import state
    from smdistributed_modelparallel_tpu.backend.topology import CP_AXIS, TP_AXIS

    smp.reset()
    smp.init({"context_parallel_degree": 2, "tensor_parallel_degree": 2,
              "ddp": True, "microbatches": 1})
    topo = state.topology
    for rank in range(topo.size):
        grp = topo.axis_group(rank, CP_AXIS)
        assert len(grp) == 2 and rank in grp
        my = topo.coords(rank)
        for r in grp:
            c = topo.coords(r)
            assert all(c[a] == my[a] for a in topo.axis_names if a != CP_AXIS)
    tp_grp = topo.axis_group(0, TP_AXIS)
    assert tp_grp == list(state.core.get_tp_group(0))
    assert state.core.get_cp_group(0) == topo.axis_group(0, CP_AXIS)


def test_instance_queries():
    """smp.instance_id / is_in_same_instance / is_multi_node (reference
    backend/core.py:479-489): ranks map to mesh devices; an "instance" is
    the host (jax process) owning the device. Single-process tier: every
    rank is on instance 0."""
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )

    smp.reset()
    smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
              "ddp": True, "microbatches": 1})
    assert smp.instance_id() == jax.process_index()
    for r in range(smp.size()):
        assert smp.instance_id(r) == 0
        assert smp.is_in_same_instance(r)
    assert smp.is_multi_node() == (jax.process_count() > 1)
    with pytest.raises(SMPValidationError):
        smp.instance_id(smp.size())
    with pytest.raises(SMPValidationError):
        smp.instance_id(-1)


def test_rank_conversions():
    """smp.{pp,tp,rdp,dp,mp}_rank_to_rank (reference backend/core.py:
    439-477): invert the per-axis rank queries within this process's
    other-axis groups, for every placement strategy."""
    for placement in ("cluster", "spread"):
        smp.reset()
        smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
                  "ddp": True, "microbatches": 1,
                  "placement_strategy": placement})
        topo_size = smp.size()
        rk = smp.rank()
        # Round-trips: converting this rank's own per-axis rank yields
        # this rank back.
        assert smp.pp_rank_to_rank(smp.pp_rank()) == rk
        assert smp.tp_rank_to_rank(smp.tp_rank()) == rk
        assert smp.rdp_rank_to_rank(smp.rdp_rank()) == rk
        assert smp.dp_rank_to_rank(smp.dp_rank()) == rk
        assert smp.mp_rank_to_rank(smp.mp_rank()) == rk
        # Structural: pp_rank_to_rank enumerates this rank's pp group in
        # stage order; dp/mp likewise enumerate their composite groups.
        from smdistributed_modelparallel_tpu.backend.state import state
        ranker = state.topology.ranker
        pp_group = [smp.pp_rank_to_rank(i) for i in range(smp.pp_size())]
        assert sorted(pp_group) == sorted(smp.get_pp_group())
        assert [ranker.get_pp_rank(r) for r in pp_group] == list(
            range(smp.pp_size())
        )
        dp_group = [smp.dp_rank_to_rank(i) for i in range(smp.dp_size())]
        assert sorted(dp_group) == sorted(smp.get_dp_group())
        mp_group = [smp.mp_rank_to_rank(i) for i in range(smp.mp_size())]
        assert sorted(mp_group) == sorted(smp.get_mp_group())
        assert all(0 <= r < topo_size for r in pp_group + dp_group + mp_group)
        # No silent numpy wraparound or raw IndexError: out-of-range
        # per-axis ranks raise the API's validation error.
        from smdistributed_modelparallel_tpu.utils.exceptions import (
            SMPValidationError,
        )
        for fn, size in ((smp.pp_rank_to_rank, smp.pp_size()),
                         (smp.tp_rank_to_rank, smp.tp_size()),
                         (smp.rdp_rank_to_rank, smp.rdp_size()),
                         (smp.dp_rank_to_rank, smp.dp_size()),
                         (smp.mp_rank_to_rank, smp.mp_size())):
            with pytest.raises(SMPValidationError):
                fn(-1)
            with pytest.raises(SMPValidationError):
                fn(size)


def test_public_surface_queries():
    """Smoke every public rank/size/group/barrier query through the smp
    surface (several were previously only exercised via state.core) on a
    cp2 x pp2 x tp2 mesh — values must be mutually consistent."""
    smp.reset()
    smp.init({"context_parallel_degree": 2, "pipeline_parallel_degree": 2,
              "tensor_parallel_degree": 2, "ddp": True, "microbatches": 3})
    assert smp.local_rank() == 0
    assert smp.local_size() == jax.local_device_count()
    assert 0 <= smp.cp_rank() < smp.cp_size() == 2
    assert smp.num_microbatches() == 3
    assert smp.process_index() == 0 and smp.process_count() == 1
    assert not smp.is_tracing()
    tp_group = smp.get_tp_group()
    rdp_group = smp.get_rdp_group()
    assert len(tp_group) == 2 and smp.rank() in tp_group
    assert smp.rank() in rdp_group
    # Single-process tier: subgroup barriers complete without peers.
    smp.mp_barrier()
    smp.tp_barrier()
    smp.rdp_barrier()
    # get_partition reflects the partitioner's ASSIGNMENT (stage 0 until
    # a step has partitioned; pin honoring is covered in
    # test_config_honored) and validates its argument type.
    assert smp.get_partition("transformer/layer0") == 0
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )
    with pytest.raises(SMPValidationError):
        smp.get_partition(123)


def test_rank_and_size_queries_follow_the_mesh_not_the_host():
    """A mesh over a subset of the host's devices (the four-chip smoke pins
    four of the test host's eight): sizes count the mesh's devices."""
    smp.reset()
    smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
              "ddp": True, "microbatches": 2}, devices=jax.devices()[4:8])
    assert smp.size() == 4 and smp.local_size() == 4
    assert smp.rank() == 0 and smp.rdp_size() == 1
    assert [d.id for d in smp.get_mesh().devices.flat] == [4, 5, 6, 7]
