"""Multi-device checks, run in ONE subprocess with 8 fake host devices
(tests/test_distributed.py drives this; keeping them in one process
amortises jax startup).  Prints "PASS <name>" per check; exits nonzero on
any failure."""

from repro.util import env

env.configure(host_device_count=8, platform="cpu")   # before any jax import

import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cb
from repro.core import aunmf, faun, naive
from repro.distributed import compression, sharding as shard_rules
from repro.distributed.pipeline import pipeline_apply
from repro.models import lm, moe as moe_lib
from repro.optim.optimizers import OptConfig
from repro.roofline.hlo import collective_stats
from repro.train import steps as steps_lib
from repro.util.compat import make_mesh, shard_map

FAILURES = []


def check(name):
    def deco(fn):
        try:
            fn()
            print(f"PASS {name}", flush=True)
        except Exception:
            FAILURES.append(name)
            print(f"FAIL {name}", flush=True)
            traceback.print_exc()
    return deco


KEY = jax.random.PRNGKey(7)
M, N, K = 96, 64, 6
A = (jax.random.uniform(KEY, (M, K))
     @ jax.random.uniform(jax.random.fold_in(KEY, 2), (K, N))
     + 0.01 * jax.random.uniform(jax.random.fold_in(KEY, 3), (M, N)))


@check("faun_matches_serial_all_algos")
def _():
    for algo in ["mu", "hals", "bpp"]:
        ref = aunmf.fit(A, K, algo=algo, iters=10, key=KEY)
        grid = faun.make_faun_mesh(4, 2)
        dist = faun.fit(A, K, grid=grid, algo=algo, iters=10, key=KEY)
        np.testing.assert_allclose(ref.W, dist.W, atol=5e-4)
        np.testing.assert_allclose(np.asarray(ref.rel_errors),
                                   np.asarray(dist.rel_errors), atol=1e-4)


@check("naive_matches_serial")
def _():
    mesh = make_mesh((8,), ("p",))
    for algo in ["mu", "bpp"]:
        ref = aunmf.fit(A, K, algo=algo, iters=8, key=KEY)
        nv = naive.fit(A, K, mesh=mesh, algo=algo, iters=8, key=KEY)
        np.testing.assert_allclose(ref.W, nv.W, atol=5e-4)


@check("faun_multipod_grid")
def _():
    mesh3 = make_mesh((2, 2, 2), ("pod", "pr", "pc"))
    grid3 = faun.FaunGrid(mesh=mesh3, row_axes=("pod", "pr"), col_axis="pc")
    ref = aunmf.fit(A, K, algo="bpp", iters=8, key=KEY)
    d3 = faun.fit(A, K, grid=grid3, algo="bpp", iters=8, key=KEY)
    np.testing.assert_allclose(ref.W, d3.W, atol=5e-4)


@check("faun_pallas_kernels")
def _():
    grid = faun.make_faun_mesh(2, 2)
    ref = aunmf.fit(A, K, algo="hals", iters=5, key=KEY)
    dist = faun.fit(A, K, grid=grid, algo="hals", iters=5, key=KEY,
                    use_pallas=True)
    np.testing.assert_allclose(ref.W, dist.W, atol=5e-4)


@check("faun_hlo_has_papers_collectives")
def _():
    grid = faun.make_faun_mesh(4, 2)
    lowered = faun.lower_step(grid, 64, 32, 4, algo="mu")
    txt = lowered.compile().as_text()
    st = collective_stats(txt)
    assert st.counts["all-gather"] >= 2, st.counts       # lines 5, 11
    assert st.counts["all-reduce"] >= 2, st.counts       # lines 4, 10
    assert st.counts["reduce-scatter"] >= 2, st.counts   # lines 7, 13


@check("faun_grid_shape_tradeoff")
def _():
    # paper Fig 7: comm volume varies with grid shape; for square-ish A the
    # 2D grid beats both 1D grids.
    m, n, k = 256, 256, 8
    vols = {}
    for pr, pc in [(8, 1), (4, 2), (2, 4), (1, 8)]:
        grid = faun.make_faun_mesh(pr, pc)
        txt = faun.lower_step(grid, m, n, k, algo="mu").compile().as_text()
        vols[(pr, pc)] = collective_stats(txt).total_wire_bytes
    assert min(vols[(4, 2)], vols[(2, 4)]) < vols[(8, 1)], vols
    assert min(vols[(4, 2)], vols[(2, 4)]) < vols[(1, 8)], vols


@check("moe_ep_matches_local")
def _():
    cfg = cb.get_reduced_config("dbrx_132b")
    import dataclasses
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))
    mesh = make_mesh((2, 4), ("data", "model"))
    p = moe_lib.init_moe(jax.random.fold_in(KEY, 9), cfg)
    x = jax.random.normal(jax.random.fold_in(KEY, 10), (4, 16, cfg.d_model))
    y_loc, aux_loc = moe_lib.moe_local(p, x, cfg)
    y_ep, aux_ep = moe_lib.moe_ep(p, x, cfg, mesh, data_axes=("data",),
                                  model_axis="model")
    # EP shards tokens over model (different capacity partition);  with a
    # generous capacity factor both are dropless -> identical outputs.
    np.testing.assert_allclose(np.asarray(y_loc), np.asarray(y_ep),
                               atol=2e-5)


@check("train_step_sharded_matches_single")
def _():
    cfg = cb.get_reduced_config("smollm_135m")
    opt = OptConfig(kind="adamw", lr=1e-3, warmup_steps=1, total_steps=10)
    state = steps_lib.init_train_state(cfg, opt, KEY)
    batch = {"tokens": jax.random.randint(KEY, (8, 32), 0, cfg.vocab),
             "labels": jax.random.randint(KEY, (8, 32), 0, cfg.vocab)}
    ref_step = jax.jit(steps_lib.make_train_step(cfg, opt))
    sref, mref = ref_step(state, batch)

    mesh = make_mesh((4, 2), ("data", "model"))
    ssh = steps_lib.state_shardings(jax.eval_shape(lambda: state), mesh)
    rt = steps_lib.make_runtime(mesh)
    dstep = jax.jit(steps_lib.make_train_step(cfg, opt, rt=rt),
                    in_shardings=(ssh, None), out_shardings=(ssh, None))
    sd, md = dstep(jax.device_put(state, ssh), batch)
    assert abs(float(mref["loss"]) - float(md["loss"])) < 1e-4
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     sref["params"], sd["params"])
    assert max(jax.tree.leaves(d)) < 5e-4, max(jax.tree.leaves(d))


@check("pipeline_matches_sequential")
def _():
    mesh = make_mesh((4,), ("pp",))
    n_stages, mb, nm, dim = 4, 4, 8, 16
    keys = jax.random.split(jax.random.fold_in(KEY, 11), n_stages)
    stage_params = {"w": jnp.stack([
        jax.random.normal(k, (dim, dim)) / dim ** 0.5 for k in keys])}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    x = jax.random.normal(jax.random.fold_in(KEY, 12), (nm, mb, dim))
    y_pipe = pipeline_apply(stage_fn, stage_params, x, mesh, "pp")
    y_seq = x
    for s in range(n_stages):
        y_seq = stage_fn({"w": stage_params["w"][s]}, y_seq)
    np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_seq),
                               atol=1e-5)


@check("pipeline_grads_flow")
def _():
    mesh = make_mesh((4,), ("pp",))
    keys = jax.random.split(jax.random.fold_in(KEY, 13), 4)
    stage_params = {"w": jnp.stack([
        jax.random.normal(k, (8, 8)) / 8 ** 0.5 for k in keys])}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    x = jax.random.normal(jax.random.fold_in(KEY, 14), (4, 2, 8))

    def loss(sp):
        y = pipeline_apply(stage_fn, sp, x, mesh, "pp")
        return jnp.mean(y ** 2)

    g = jax.grad(loss)(stage_params)
    gseq = jax.grad(lambda sp: jnp.mean(
        jnp.tanh(jnp.tanh(jnp.tanh(jnp.tanh(
            x @ sp["w"][0]) @ sp["w"][1]) @ sp["w"][2]) @ sp["w"][3]) ** 2
    ))(stage_params)
    np.testing.assert_allclose(np.asarray(g["w"]), np.asarray(gseq["w"]),
                               atol=1e-5)


@check("compressed_pmean_error_feedback")
def _():
    mesh = make_mesh((8,), ("dp",))
    from jax.sharding import PartitionSpec as P

    g_all = jax.random.normal(jax.random.fold_in(KEY, 15), (8, 64))
    true_mean = jnp.mean(g_all, axis=0)

    def body(g, r):
        est, new_res = compression.compressed_pmean(
            {"g": g[0]}, {"g": r[0]}, "dp")
        return est["g"], new_res["g"][None]

    fn = shard_map(body, mesh, in_specs=(P("dp"), P("dp")),
                   out_specs=(P(), P("dp")))
    r = jnp.zeros((8, 1, 64))
    est, r = fn(g_all.reshape(8, 1, 64), r)
    err1 = float(jnp.max(jnp.abs(est - true_mean)))
    # one more round with feedback: residual re-injected reduces bias
    est2, _ = fn(jnp.zeros((8, 1, 64)), r)
    combined = est + est2
    err2 = float(jnp.max(jnp.abs(combined - true_mean)))
    assert err1 < 0.05, err1           # int8 quantisation error bound
    assert err2 < err1 + 1e-6, (err1, err2)  # feedback recovers residual


@check("elastic_remesh_restore")
def _():
    import tempfile
    from repro.checkpoint import checkpoint as ckpt_lib
    from repro.train.loop import elastic_resume

    cfg = cb.get_reduced_config("smollm_135m")
    opt = OptConfig(kind="adamw")
    state = steps_lib.init_train_state(cfg, opt, KEY)
    with tempfile.TemporaryDirectory() as d:
        ckpt_lib.save(state, 5, d)
        devs = jax.devices()[:4]       # "lost" half the devices
        restored, step, mesh = elastic_resume(
            state, d, devs, prefer_model=2,
            make_shardings=lambda m: steps_lib.state_shardings(
                jax.eval_shape(lambda: state), m))
        assert step == 5
        assert mesh.devices.size == 4
        d0 = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))),
            state["params"], restored["params"])
        assert max(jax.tree.leaves(d0)) == 0.0


@check("per_arch_sharded_train_lowering")
def _():
    """Every architecture family's train step must lower+compile with the
    production sharding rules on a small (pod,data,model) mesh — the
    same code path as the 512-chip dry-run, exercised per family:
    enc-dec (whisper), hybrid recurrent (recurrentgemma), MoE-EP (dbrx),
    xLSTM (ssm), gated cross-attention (vision)."""
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    for arch in ["whisper_base", "recurrentgemma_9b", "dbrx_132b",
                 "xlstm_125m", "llama32_vision_90b"]:
        cfg = cb.get_reduced_config(arch).replace(remat=True)
        opt = OptConfig(kind="adamw")
        rt = steps_lib.make_runtime(mesh)
        spec = steps_lib.train_state_specs(cfg, opt)
        ssh = steps_lib.state_shardings(spec, mesh)
        B, S = 8, 32
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if cfg.is_encdec:
            batch["enc_frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                       jnp.float32)
        if cfg.frontend == "image_patches":
            batch["img_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.num_image_tokens, cfg.d_model), jnp.float32)
        bsh = steps_lib.batch_shardings(batch, mesh)
        step = steps_lib.make_train_step(cfg, opt, rt=rt, microbatches=2)
        jax.jit(step, in_shardings=(ssh, bsh),
                out_shardings=(ssh, None)).lower(spec, batch).compile()


@check("decode_cache_shardings_lower")
def _():
    cfg = cb.get_reduced_config("qwen2_72b")
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    shape = cb.ShapeConfig("t", 64, 8, "decode")
    specs = lm.input_specs(cfg, shape)
    cache_sh = shard_rules.cache_shardings(specs["caches"], mesh, 8)
    pspec = jax.eval_shape(lambda: lm.init_params(cfg, KEY))
    pshard = shard_rules.param_shardings(pspec, mesh)
    rt = steps_lib.make_runtime(mesh)
    step = steps_lib.make_serve_step(cfg, rt=rt)
    from jax.sharding import NamedSharding, PartitionSpec as P
    jitted = jax.jit(step, in_shardings=(
        pshard, cache_sh,
        NamedSharding(mesh, P(("pod", "data"), None)),
        NamedSharding(mesh, P())))
    jitted.lower(pspec, specs["caches"], specs["tokens"],
                 specs["pos"]).compile()


if __name__ == "__main__":
    print(f"\n{len(FAILURES)} failures: {FAILURES}")
    sys.exit(1 if FAILURES else 0)
