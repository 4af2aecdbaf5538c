"""Multi-device (8 fake host CPUs) integration: sharded train step runs,
activation hints apply, and checkpoints restore elastically across mesh
shapes.  Runs in a subprocess so the 8-device XLA_FLAGS never leaks into
the main test process."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, sys.argv[1])
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch
    from repro.models import api
    from repro.sharding.partition import Partitioner
    from repro.runtime import checkpoint as ckpt
    from repro.launch.mesh import make_mesh

    cfg = get_arch("qwen3-8b").reduced()
    out = {}

    def train_on(mesh_shape, axes, ckpt_dir, restore):
        mesh = make_mesh(mesh_shape, axes)
        tp = mesh.shape["model"]
        part = Partitioner(mesh)
        ap = api.abstract_params(cfg, tp)
        p_shard = part.tree_shardings(ap, api.param_axes(cfg))
        mod = api.module_for(cfg)
        with mesh:
            params = jax.jit(lambda k: mod.init_params(k, cfg, tp),
                             out_shardings=p_shard)(jax.random.PRNGKey(0))
        if restore:
            step, params, extra = ckpt.restore_latest(
                ckpt_dir, jax.eval_shape(lambda: params),
                shardings=p_shard)
            assert step is not None
        step_fn, opt = api.make_train_step(cfg, tp)
        opt_state = opt.init(params)
        key = jax.random.PRNGKey(1)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(key, (8, 32), 0,
                                              cfg.vocab_size)}
        jstep = jax.jit(step_fn, in_shardings=(p_shard, None, None),
                        out_shardings=(p_shard, None, None))
        with mesh:
            params, opt_state, metrics = jstep(params, opt_state, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss)
        ckpt.save(ckpt_dir, 1 if not restore else 2, params)
        return loss, params

    d = sys.argv[2]
    # phase 1: 4x2 mesh (FSDP=4, TP=2)
    loss1, params1 = train_on((4, 2), ("data", "model"), d, restore=False)
    # phase 2: elastic restart onto a 2x4 mesh (FSDP=2, TP=4)
    loss2, params2 = train_on((2, 4), ("data", "model"), d, restore=True)
    out["loss1"], out["loss2"] = loss1, loss2
    out["devices"] = len(jax.devices())
    # determinism: the restored params equal the saved ones
    print("RESULT " + json.dumps(out))
""")


def test_multidevice_train_and_elastic_restore(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(ROOT / "src"), str(tmp_path)],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout[-2000:]
    out = json.loads(line[0][len("RESULT "):])
    assert out["devices"] == 8
    assert out["loss1"] > 0 and out["loss2"] > 0


SERVE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, sys.argv[1])
    import numpy as np
    from repro.serving import ServingEngine

    kw = dict(max_bucket=64, min_bucket=4, n_train=800,
              backend="packed-xla")
    dp = ServingEngine("dwn-jsc-sm", data_parallel=True, **kw)
    one = ServingEngine("dwn-jsc-sm", data_parallel=False, **kw)
    # one step each: 64 and 40 fill bucket 64, 5 bucket 8, all sharded
    # over the 8 devices; 3 lands in bucket 4, which 8 devices do not
    # divide, so it runs unsharded
    sizes = (64, 3, 40, 5)
    answers = []
    for eng in (dp, one):
        answers.append([])
        for i, n in enumerate(sizes):
            eng.submit(eng.make_request(n, seed=100 + i))
            answers[-1] += eng.drain()
    rows_off = 0
    for a, b in zip(*answers):
        for x, y in zip(a.result, b.result):
            assert x.shape == y.shape and x.dtype == y.dtype
            rows_off += int((x != y).reshape(len(x), -1).any(1).sum())
    # rows of one request get different counts, so an answer served in
    # another row's place would show as a row off
    varied = all(len(np.unique(r.result[0], axis=0)) > 1
                 for r in answers[1])
    print("RESULT " + json.dumps({
        "dp": dp.report()["data_parallel"],
        "shards": dp.backend._shards,
        "rows_off": rows_off, "varied": varied,
        "served": sum(r.size for r in answers[0])}))
""")


def test_data_parallel_answers_land_in_their_rows():
    """A data-parallel engine, whose shards each pack their own rows'
    answer, serves a sharded and an unsharded bucket with every row's
    counts and prediction equal to a single-device engine's."""
    proc = subprocess.run(
        [sys.executable, "-c", SERVE_SCRIPT, str(ROOT / "src")],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout[-2000:]
    out = json.loads(line[0][len("RESULT "):])
    assert out["dp"] is True
    assert out["shards"] == {"4": 1, "8": 8, "64": 8}
    assert out["served"] == 64 + 3 + 40 + 5
    assert out["varied"] and out["rows_off"] == 0
