"""Elastic data-parallel subsystem (repro.distributed).

The bit-level guarantees run in subprocesses with 8 fake CPU devices
(XLA_FLAGS set before jax import — this session keeps its single device,
same pattern as test_accumulation.py); planner/scheduler/accountant logic
is pure Python and tested in-process.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # benchmarks/

from repro.core.schedules import SEBS, ClassicalStagewise
from repro.core.stages import StageController
from repro.core.trainer import TrainLog
from repro.distributed import (
    CommAccountant,
    ElasticMeshPlanner,
    SyncScheduler,
    allgather_bytes_per_device,
    allreduce_bytes_per_device,
    span_tree_sum,
)


# -- planner ----------------------------------------------------------------


def test_planner_widths_follow_the_stage_ladder():
    """rho=2: width doubles per stage up to the budget, then local
    accumulation absorbs the rest — global accum is always preserved."""
    sched = SEBS(b1=4, C1=64, rho=2.0, num_stages=5, eta=0.1)
    ctl = StageController(sched, microbatch=4)
    planner = ElasticMeshPlanner(device_budget=4, devices=list(range(8)))
    ladder = ctl.stage_ladder()
    assert [p.stage for p in ladder] == [0, 1, 2, 3, 4]
    plans = [planner.plan_for(p) for p in ladder]
    assert [mp.width for mp in plans] == [1, 2, 4, 4, 4]
    assert [mp.local_accum for mp in plans] == [1, 1, 1, 2, 4]
    for sp, mp in zip(ladder, plans):
        assert mp.width * mp.local_accum == sp.accum_steps


def test_planner_non_power_of_two_accum_degrades_to_dividing_width():
    planner = ElasticMeshPlanner(device_budget=8, devices=list(range(8)))
    assert planner.width_for(1) == 1
    assert planner.width_for(3) == 1   # odd: nothing divides
    assert planner.width_for(6) == 2   # 2 | 6, 4 does not
    assert planner.width_for(12) == 4
    assert planner.width_for(32) == 8  # capped at budget


def test_planner_budget_capped_by_real_devices():
    planner = ElasticMeshPlanner(device_budget=64, devices=list(range(4)))
    assert planner.device_budget == 4
    with pytest.raises(ValueError):
        ElasticMeshPlanner(device_budget=0)


# -- canonical reduction tree ----------------------------------------------


@pytest.mark.parametrize("n,width", [(4, 2), (8, 4), (12, 4), (6, 2), (16, 8)])
def test_span_tree_sum_is_width_invariant(n, width):
    """Chunked tree-sum + tree-combine == the width-1 tree, bit for bit —
    the host-side model of what the elastic step does across devices."""
    rng = np.random.default_rng(0)
    terms = [np.float32(rng.standard_normal()) for _ in range(n)]
    full = span_tree_sum(lambda i: terms[i], n)
    chunk = n // width
    partials = [
        span_tree_sum(lambda i, d=d: terms[d * chunk + i], chunk)
        for d in range(width)
    ]
    combined = span_tree_sum(lambda d: partials[d], width)
    assert np.float32(combined).tobytes() == np.float32(full).tobytes()


def test_span_tree_sum_differs_from_serial_order():
    """The guarantee is meaningful: the canonical tree is NOT just serial
    summation in disguise (otherwise chunking would have been unsafe)."""
    rng = np.random.default_rng(3)
    terms = [np.float32(x) for x in rng.standard_normal(16) * 1e3]
    serial = np.float32(0)
    for t in terms:
        serial = np.float32(serial + t)
    tree = span_tree_sum(lambda i: terms[i], 16)
    assert float(tree) == pytest.approx(float(serial), rel=1e-5)


# -- sync scheduler + accountant -------------------------------------------


def test_sync_scheduler_stage_keyed_interval():
    s = SyncScheduler(mode="local", local_interval=2, local_growth=2.0)
    assert [s.interval(k) for k in range(4)] == [2, 4, 8, 16]
    assert s.due(4, 2, 0) and not s.due(3, 2, 1)
    assert SyncScheduler(mode="exact").interval(5) == 1
    with pytest.raises(ValueError):
        SyncScheduler(mode="bogus")


def test_byte_models():
    assert allgather_bytes_per_device(100, 1) == 0
    assert allgather_bytes_per_device(100, 4) == 300
    assert allreduce_bytes_per_device(100, 1) == 0
    assert allreduce_bytes_per_device(100, 4) == 150


def test_accountant_roundtrip_through_json_meta():
    import json

    a = CommAccountant()
    a.record_update(0, collectives=0)
    a.record_update(1, collectives=1, bytes_moved=64)
    a.record_reshard(1, bytes_moved=32)
    b = CommAccountant()
    b.restore(json.loads(json.dumps(a.state())))  # stage keys survive str()
    assert b.summary() == a.summary()
    assert b.total_bytes == 96 and b.total_sync_events == 1
    assert b.total("updates") == 2


# -- TrainLog comm fields (satellite: survive checkpoint/resume) ------------


def test_trainlog_comm_fields_roundtrip():
    log = TrainLog(steps=[1, 2], samples=[4, 8], stages=[0, 0],
                   batch_sizes=[4, 4], losses=[1.0, 0.9],
                   noise_scales=[0.1, 0.2], comm_bytes=[0, 128], sync_events=[0, 2])
    clone = TrainLog.from_dict(log.as_dict())
    assert clone == log


def test_trainlog_from_legacy_dict_pads_comm_fields():
    d = {"steps": [1, 2], "samples": [4, 8], "stages": [0, 0],
         "batch_sizes": [4, 4], "losses": [1.0, 0.9], "noise_scales": [0.1, 0.2]}
    log = TrainLog.from_dict(d)
    assert log.comm_bytes == [0, 0] and log.sync_events == [0, 0]


# -- table_comm accounting (acceptance invariant, no training) --------------


def test_sebs_strictly_fewer_syncs_than_classical():
    from benchmarks.table_comm import account

    sebs = SEBS(b1=64, C1=960, rho=2.0, num_stages=4, eta=0.1)
    cls = ClassicalStagewise(b=64, C1=960, rho=2.0, num_stages=4, eta1=0.1)
    a_sebs = account(sebs, "exact", grad_bytes=1000, state_bytes=2000)
    a_cls = account(cls, "exact", grad_bytes=1000, state_bytes=2000)
    assert a_sebs.total("sync_events") < a_cls.total("sync_events")
    assert a_sebs.total("updates") < a_cls.total("updates")
    assert a_sebs.total("bytes") < a_cls.total("bytes")
    # local mode strictly cheaper than exact for the same schedule
    a_local = account(sebs, "local", grad_bytes=1000, state_bytes=2000)
    assert a_local.total("sync_events") < a_sebs.total("sync_events")


# -- subprocess properties on 8 fake devices --------------------------------


def _run_sub(script: str) -> str:
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, cwd="."
    )
    assert res.returncode == 0, res.stdout + res.stderr
    return res.stdout


_PRELUDE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.core import SEBS, SEBSTrainer
    from repro.data import DataPipeline, TokenDataset
    from repro.distributed import ElasticTrainer
    from repro.models import build_model
    from repro.optim import make_optimizer
    from repro.train.state import TrainState

    cfg = get_config("qwen2.5-3b", "smoke").replace(compute_dtype="float32")
    model = build_model(cfg)

    def make(budget, sync_mode="exact", param_axes=None, **kw):
        opt = make_optimizer("momentum", beta=0.9)
        schedule = SEBS(b1=4, C1=16, rho=2.0, num_stages=3, eta=0.05)
        ds = TokenDataset(vocab_size=cfg.vocab_size, seq_len=8, seed=0)
        tr = ElasticTrainer(model, opt, schedule, DataPipeline(ds), microbatch=4,
                            grad_clip=1.0, sync_mode=sync_mode,
                            device_budget=budget, param_axes=param_axes, **kw)
        params, _ = model.init(jax.random.key(0))
        return tr, TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))

    def pbytes(s):
        return [np.asarray(x).tobytes() for x in jax.tree.leaves(s.params)]
    """
)


_WIDTH_SCRIPT = _PRELUDE + textwrap.dedent(
    """
    runs = {}
    for budget in (1, 2, 4):
        tr, st = make(budget)
        st, log = tr.run(st, log_every=1)
        runs[budget] = (pbytes(st), log)
        widths = sorted({k[1] for k in tr._steps})
        assert max(widths) == min(budget, 4), (budget, widths)

    p1, l1 = runs[1]
    for budget in (2, 4):
        p, l = runs[budget]
        # the guarantee: bit-identical losses, stages, GNS and params at
        # every width, INCLUDING across elastic width changes at stage
        # boundaries (budget 4 transitions 1 -> 2 -> 4 mid-run)
        assert l.losses == l1.losses, (budget, l.losses, l1.losses)
        assert l.stages == l1.stages and l.batch_sizes == l1.batch_sizes
        np.testing.assert_array_equal(l.noise_scales, l1.noise_scales)
        assert p == p1, budget

    # comm was accounted and monotone at widths > 1
    _, l4 = runs[4]
    assert l4.comm_bytes[-1] > 0 and l4.sync_events[-1] > 0
    assert l4.comm_bytes == sorted(l4.comm_bytes)
    assert runs[1][1].comm_bytes[-1] == 0  # width 1 moves nothing

    # rule-based storage sharding is placement-only: same bits
    params, axes = model.init(jax.random.key(0))
    tr, st = make(4, param_axes=axes)
    st, log = tr.run(st, log_every=1)
    assert log.losses == l1.losses and pbytes(st) == p1

    # sanity vs the single-process trainer (different reduction order ->
    # allclose, not bitwise)
    opt = make_optimizer("momentum", beta=0.9)
    schedule = SEBS(b1=4, C1=16, rho=2.0, num_stages=3, eta=0.05)
    ds = TokenDataset(vocab_size=cfg.vocab_size, seq_len=8, seed=0)
    base = SEBSTrainer(model, opt, schedule, DataPipeline(ds), mesh=None,
                       microbatch=4, mode="accumulate", accum_mode="psum_each",
                       grad_clip=1.0)
    params, _ = model.init(jax.random.key(0))
    bst = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    bst, blog = base.run(bst, log_every=1)
    np.testing.assert_allclose(l1.losses, blog.losses, rtol=1e-4)
    print("WIDTH_EQUIVALENCE_OK", len(l1.losses))
    """
)


def test_exact_sync_width_equivalence_bitwise():
    """Acceptance property: exact-sync elastic training at data-axis widths
    {1, 2, 4} produces bit-identical losses, stage transitions and final
    params, including across elastic width changes at stage boundaries."""
    out = _run_sub(_WIDTH_SCRIPT)
    assert "WIDTH_EQUIVALENCE_OK 12" in out


_LOCAL_SCRIPT = _PRELUDE + textwrap.dedent(
    """
    import tempfile
    from repro.checkpoint import CheckpointManager

    # save_every=3 deliberately misaligned with local_interval=2: periodic
    # saves must SNAP to the next replica-consistent update (a width-1
    # stage or right after an average), never be dropped
    tr, st = make(4, sync_mode="local", local_interval=2)
    with tempfile.TemporaryDirectory() as td:
        with CheckpointManager(td, keep_last=10) as ck:
            st, log = tr.run(st, log_every=1, checkpointer=ck, save_every=3)
            steps = sorted(
                int(d.split("_")[1]) for d in os.listdir(td) if d.startswith("step_")
            )
    assert all(np.isfinite(log.losses)), log.losses
    assert tr.accountant.total_sync_events > 0
    assert tr.accountant.total("collectives") < tr.accountant.total("updates")
    # finalize collapsed the replica axis: leaves have param shapes again
    ref, _ = model.init(jax.random.key(0))
    assert all(a.shape == b.shape
               for a, b in zip(jax.tree.leaves(st.params), jax.tree.leaves(ref)))
    # the update-9 save (stage 2, mid-drift) snapped to the sync at 10; the
    # final state at 12 reached disk even though 12 is not a save multiple
    assert steps == [3, 6, 10, 12], steps
    print("LOCAL_SGD_OK", len(steps))
    """
)


def test_local_sgd_mode_runs_syncs_and_checkpoints():
    out = _run_sub(_LOCAL_SCRIPT)
    assert "LOCAL_SGD_OK" in out


_POD_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim import make_optimizer
    from repro.train.state import TrainState
    from repro.train.step import build_train_step

    cfg = get_config("qwen2.5-3b", "smoke").replace(compute_dtype="float32")
    model = build_model(cfg)
    opt = make_optimizer("sgd")
    params, _ = model.init(jax.random.key(0))
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    # pod is pure data parallelism (cf. make_production_mesh)
    mesh = make_host_mesh(data=2, model=1, pod=2)
    assert mesh.axis_names == ("pod", "data", "model")
    assert mesh.shape["pod"] == 2
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    stacked = {"tokens": tokens.reshape(2, 4, 16)}
    step_d = build_train_step(model, opt, mesh, accum_steps=2,
                              mode="deferred", donate=False)
    sd, md = step_d(state, stacked, jnp.float32(0.1), jnp.int32(0))
    step_p = build_train_step(model, opt, mesh=None, accum_steps=2, donate=False)
    sp, mp = step_p(state, stacked, jnp.float32(0.1), jnp.int32(0))
    assert abs(float(md["loss"]) - float(mp["loss"])) < 1e-3, (md["loss"], mp["loss"])
    for a, b in zip(jax.tree.leaves(sd.params), jax.tree.leaves(sp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-4)
    print("POD_DEFERRED_OK")
    """
)


def test_host_mesh_pod_axis_deferred_psum():
    """Satellite: make_host_mesh can now build a pod axis, making the
    multi-pod deferred-psum path (one collective across ("pod", "data")
    per update) testable on CPU."""
    out = _run_sub(_POD_SCRIPT)
    assert "POD_DEFERRED_OK" in out


def test_make_host_mesh_default_unchanged():
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, 1)
    assert mesh.axis_names == ("data", "model")


def test_make_data_mesh_bounds():
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(1)
    assert mesh.axis_names == ("data",) and mesh.shape["data"] == 1
    with pytest.raises(ValueError):
        make_data_mesh(0)
    with pytest.raises(ValueError):
        make_data_mesh(10_000)
