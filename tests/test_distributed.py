"""Multi-device integration tests (subprocesses with 8 host devices):
elastic reconfiguration, MPMD heterogeneous pipeline, sharded train step,
and a small-mesh dry-run including HLO collective parsing.
"""
import json

import pytest

from helpers import run_py

pytestmark = pytest.mark.slow


def test_elastic_resize_and_rollback(tmp_path):
    out = run_py(f"""
        import jax
        from repro.configs import get_config
        from repro.train.elastic import ElasticTrainer
        from repro.train import optimizer as opt_lib, data as data_lib
        cfg = get_config("smollm_360m").reduced()
        tr = ElasticTrainer(
            cfg, opt_lib.OptimizerConfig(lr=1e-3, warmup_steps=2,
                                         total_steps=40),
            data_lib.DataConfig(seq_len=16, global_batch=8,
                                num_microbatches=1),
            workdir={str(tmp_path)!r}, checkpoint_every=5)
        log = tr.train(16, events=[(6, 4, False), (12, 8, True)])
        kinds = [r["kind"] for r in tr.reconfigs]
        assert kinds == ["kill-free", "rollback"], tr.reconfigs
        # rollback at step 12 restored the step-10 checkpoint, so steps
        # 10-11 re-run: 16 unique steps + 2 replayed
        assert len(log) == 18, [r["step"] for r in log]
        assert log[-1]["loss"] < log[0]["loss"]
        assert tr.reconfigs[1]["step"] == 12
        assert tr.reconfigs[1]["resumed_at"] == 10
        print("OK", log[0]["loss"], log[-1]["loss"])
    """, devices=8, timeout=900)
    assert "OK" in out


def test_mpmd_pipeline_heterogeneous_tp_matches_single_program():
    out = run_py("""
        import dataclasses, jax, numpy as np, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import model as model_lib
        from repro.dist.pipeline import MPMDPipeline, even_stages
        from repro.train import optimizer as opt_lib
        cfg = dataclasses.replace(get_config("smollm_360m").reduced(),
                                  n_layers=4, tie_embeddings=False)
        stages = even_stages(cfg, tps=[4, 2], dp=1)   # heterogeneous TP!
        pipe = MPMDPipeline(cfg, stages, opt_lib.OptimizerConfig(lr=1e-3))
        rng = np.random.default_rng(0)
        NM, B, S = 2, 4, 16
        toks = rng.integers(0, cfg.vocab_size, (NM, B, S+1)).astype(np.int32)
        batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
        full = pipe.full_params_like(jax.device_get(
            model_lib.init(cfg, jax.random.PRNGKey(9))))
        full = jax.tree_util.tree_map(jnp.asarray, full)
        flat = {k: jnp.asarray(v.reshape(NM*B, *v.shape[2:]))
                for k, v in batch.items()}
        loss_ref, _ = model_lib.loss_fn(cfg, full, flat)
        loss_pipe = pipe.train_step(batch)
        assert abs(float(loss_ref) - loss_pipe) < 1e-3, (loss_ref, loss_pipe)
        l2 = pipe.train_step(batch)
        assert l2 < loss_pipe     # it learns
        print("OK")
    """, devices=8, timeout=900)
    assert "OK" in out


def test_mpmd_stage_programs_bypass_persistent_cache(tmp_path):
    """No stage program is written to (or read from) the persistent
    compile cache, while other programs in the process still are."""
    out = run_py(f"""
        import dataclasses, os, jax, numpy as np
        from repro.configs import get_config
        from repro.dist.pipeline import MPMDPipeline, even_stages
        from repro.train import optimizer as opt_lib
        jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cfg = dataclasses.replace(get_config("smollm_360m").reduced(),
                                  n_layers=2, tie_embeddings=False)
        pipe = MPMDPipeline(cfg, even_stages(cfg, tps=[1, 1]),
                            opt_lib.OptimizerConfig(lr=1e-3))
        pipe.init_params(jax.random.PRNGKey(0))
        toks = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 2, 9)).astype(np.int32)
        pipe.train_step({{"tokens": toks[..., :-1], "labels": toks[..., 1:]}})
        def control(x):
            return x * 2
        jax.jit(control)(np.ones(4, np.float32)).block_until_ready()
        print("FILES", sorted(f.split("-")[0] for f in os.listdir(
            {str(tmp_path)!r})))
    """, devices=2, timeout=600)
    files = out.split("FILES", 1)[1]
    assert "jit_control" in files, files
    for name in ("jit_fwd", "jit_bwd_first", "jit_bwd_last", "jit_update",
                 "jit_init_state", "jit__lambda"):
        assert f"'{name}'" not in files, files


def test_sharded_train_step_matches_single_device():
    out = run_py("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import model as model_lib
        from repro.dist.mesh import data_model_mesh
        from repro.train import optimizer as opt_lib
        from repro.train.train_step import jit_train_step, make_train_step
        import dataclasses
        cfg = dataclasses.replace(get_config("qwen1_5_0_5b").reduced(),
                                  sharding="fsdp_tp")
        params = model_lib.init(cfg, jax.random.PRNGKey(0))
        opt_cfg = opt_lib.OptimizerConfig(lr=1e-3)
        opt_state = opt_lib.init_state(params)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab_size, (1, 8, 17)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks[..., :-1]),
                 "labels": jnp.asarray(toks[..., 1:])}
        # single device reference
        p1, o1, m1 = jax.jit(make_train_step(cfg, opt_cfg))(
            params, opt_state, batch)
        # 4x2 mesh (data x model)
        mesh = data_model_mesh(4, 2)
        with jax.set_mesh(mesh):
            step = jit_train_step(cfg, opt_cfg, mesh, 1, 8, donate=False)
            p2, o2, m2 = step(params, opt_state, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)
        print("OK", float(m1["loss"]))
    """, devices=8, timeout=900)
    assert "OK" in out


def test_dryrun_small_mesh_cell():
    """Full dry-run path (lower+compile+analysis) on an 8-device mesh."""
    out = run_py("""
        import json, os
        import jax
        from jax.sharding import AxisType
        import repro.launch.dryrun as dr
        import repro.launch.mesh as mesh_mod
        # shrink the production mesh for the in-test run
        mesh_mod.make_production_mesh = lambda multi_pod=False: (
            jax.make_mesh((2, 2, 2) if multi_pod else (4, 2),
                          ("pod", "data", "model") if multi_pod
                          else ("data", "model"),
                          axis_types=(AxisType.Auto,) * (3 if multi_pod
                                                         else 2)))
        import dataclasses
        import repro.configs as C
        cfg = C.get_config("smollm_360m").reduced()
        # reduced configs replicate; exercise the real sharding policy
        cfg = dataclasses.replace(cfg, sharding="fsdp_tp", dtype="bfloat16",
                                  param_dtype="bfloat16")
        C_get = C.get_config
        C.get_config = lambda name: cfg
        import repro.models.config as MC
        rec = dr.run_cell("smollm_360m", "train_4k", False, "/tmp/dr_test",
                          mesh=mesh_mod.make_production_mesh())
        assert rec["ok"], rec.get("error")
        assert rec["roofline"]["dominant"] in ("compute", "memory",
                                               "collective")
        assert rec["per_device"]["flops"] > 0
        assert rec["collectives"], "expected collective ops in sharded step"
        rec2 = dr.run_cell("smollm_360m", "decode_32k", True, "/tmp/dr_test",
                           mesh=mesh_mod.make_production_mesh(multi_pod=True))
        assert rec2["ok"], rec2.get("error")
        print("OK", rec["roofline"]["dominant"],
              sorted(rec["collectives"]))
    """, devices=8, timeout=900)
    assert "OK" in out


def test_hlo_collective_parser():
    from repro.launch.hlo import collective_bytes
    txt = """
  %all-reduce.1 = f32[16,128]{1,0} all-reduce(%dot), channel_id=1, replica_groups=[2,4]<=[8], use_global_device_ids=true
  %ag = bf16[32,64]{1,0} all-gather(%p0), channel_id=2, replica_groups=[4,2]<=[8]
  %cp = f32[8]{0} collective-permute(%x), source_target_pairs={{0,1},{1,0}}
  %rs = f32[4,4]{1,0} reduce-scatter(%y), channel_id=3, replica_groups={{0,1,2,3}}
"""
    st = collective_bytes(txt)
    assert st.by_kind["all-reduce"][0] == 1
    assert st.by_kind["all-reduce"][1] == 16 * 128 * 4
    # ring factor 2(k-1)/k with k=4
    assert abs(st.by_kind["all-reduce"][2]
               - 2 * 3 / 4 * 16 * 128 * 4) < 1e-6
    assert st.by_kind["all-gather"][1] == 32 * 64 * 2
    assert st.by_kind["collective-permute"][2] == 8 * 4
    assert st.by_kind["reduce-scatter"][0] == 1
    assert st.total_bytes > 0
