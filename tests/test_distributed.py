"""Distributed tests run in a subprocess with 8 fake devices (so the main
test process keeps its single real device; the dry-run owns 512)."""
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def run_sub(body: str) -> dict:
    prog = textwrap.dedent("""
        import os, json, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        sys.path.insert(0, {src!r})
        import jax, jax.numpy as jnp, numpy as np
        out = {{}}
    """).format(src=SRC) + textwrap.dedent(body) + \
        "\nprint('RESULT::' + json.dumps(out))\n"
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines()
            if l.startswith("RESULT::")][-1]
    return json.loads(line[len("RESULT::"):])


def test_distributed_search_matches_single_device():
    out = run_sub("""
        from repro.launch.mesh import make_test_mesh
        from repro.core.distributed import make_distributed_search
        from repro.core.types import SearchParams
        from repro.core.build import build_graph
        from repro.core.search import brute_force_topk, recall_at_k

        mesh = make_test_mesh((2, 4), ("data", "model"))
        sp = SearchParams(k=10, pool=48, max_iters=64)
        step = make_distributed_search(mesh, sp, data_axes=("data",),
                                       query_axis="model")
        N, D, R = 2000, 16, 8
        rng = np.random.default_rng(0)
        vecs = rng.normal(size=(N, D)).astype(np.float32)
        parts = [build_graph(vecs[i*1000:(i+1)*1000], R) for i in range(2)]
        idx = {
          "vectors": np.concatenate([np.asarray(g.vectors) for g in parts]),
          "nbrs": np.concatenate([np.asarray(g.nbrs) for g in parts]),
          "alive": np.concatenate([np.asarray(g.alive) for g in parts]),
          "e_in": np.concatenate([np.asarray(g.e_in) for g in parts]),
          "cache_vectors": np.zeros((2*64, D), np.float32),
          "slot_hid": np.full((2*64,), -1, np.int32),
          "h2d": np.full((N,), -1, np.int32),
          "f_recent": np.zeros((N,), np.float32),
        }
        Q = rng.normal(size=(32, D)).astype(np.float32)
        with jax.set_mesh(mesh):
            jidx = {k: jnp.asarray(v) for k, v in idx.items()}
            ids, dists = jax.jit(step)(jidx, jnp.asarray(Q),
                                       jax.random.PRNGKey(0))
            ids.block_until_ready()
        gfull = build_graph(vecs, R)
        ti, _ = brute_force_topk(gfull, jnp.asarray(Q), 10)
        out["recall"] = float(recall_at_k(jnp.asarray(np.asarray(ids)), ti))
        d = np.asarray(dists)
        out["sorted"] = bool((np.diff(d, axis=1) >= -1e-5).all())
    """)
    assert out["recall"] > 0.75
    assert out["sorted"]


def test_data_parallel_train_matches_single_device():
    out = run_sub("""
        from repro.configs.base import load_smoke_config
        from repro.models import model as Mdl
        from repro.launch.mesh import make_test_mesh
        from jax.sharding import PartitionSpec as P

        cfg = load_smoke_config("smollm_135m")
        params = Mdl.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                    cfg.vocab)
        batch = {"tokens": tokens, "labels": tokens}
        loss_single = float(Mdl.loss_fn(cfg, params, batch))

        mesh = make_test_mesh((4, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            p_spec = Mdl.param_specs(cfg)
            b_spec = {"tokens": P("data", None), "labels": P("data", None)}
            f = jax.jit(lambda p, b: Mdl.loss_fn(cfg, p, b),
                        in_shardings=(p_spec, b_spec))
            loss_sharded = float(f(params, batch))
        out["single"] = loss_single
        out["sharded"] = loss_sharded
    """)
    assert abs(out["single"] - out["sharded"]) / abs(out["single"]) < 2e-2


def test_seq_sharded_decode_attention_no_kv_allgather():
    """long-context decode: KV sharded on sequence must lower to a partial
    softmax + all-reduce (flash-decoding combine), NOT a KV all-gather."""
    out = run_sub("""
        from repro.launch.mesh import make_test_mesh
        from repro.models.layers import decode_attention
        from jax.sharding import PartitionSpec as P
        import re

        mesh = make_test_mesh((1, 8), ("data", "model"))
        B, T, H, Dh = 2, 1024, 4, 16
        q = jax.ShapeDtypeStruct((B, 1, H, Dh), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((B, T, H, Dh), jnp.bfloat16)
        with jax.set_mesh(mesh):
            low = jax.jit(lambda q, k, v: decode_attention(q, k, v, T),
                          in_shardings=(P(), P(None, "model", None, None),
                                        P(None, "model", None, None))
                          ).lower(q, kv, kv)
            txt = low.compile().as_text()
        kv_bytes = B*T*H*Dh*2
        ags = []
        for line in txt.splitlines():
            m = re.search(r'= ([a-z0-9]+)\\[([0-9,]+)\\][^ ]* all-gather', line)
            if m:
                n = 1
                for dd in m.group(2).split(','):
                    n *= int(dd)
                ags.append(n)
        out["max_allgather_elems"] = max(ags) if ags else 0
        out["kv_elems"] = B*T*H*Dh
        out["has_allreduce"] = "all-reduce" in txt
    """)
    # no all-gather anywhere near the KV size; combine happens via reduce
    assert out["max_allgather_elems"] < out["kv_elems"] // 4
    assert out["has_allreduce"]


def test_elastic_remesh_preserves_values():
    out = run_sub("""
        from repro.launch.mesh import make_test_mesh
        from repro.train.compression import remesh
        from jax.sharding import PartitionSpec as P

        big = make_test_mesh((4, 2), ("data", "model"))
        small = make_test_mesh((2, 2), ("data", "model"))
        x = jnp.arange(64.0).reshape(8, 8)
        tree = {"w": x, "b": jnp.ones((8,))}
        spec = {"w": P("data", "model"), "b": P("data")}
        with jax.set_mesh(big):
            placed = jax.tree.map(
                lambda a, s: jax.device_put(
                    a, jax.NamedSharding(big, s)), tree, spec)
        moved = remesh(placed, spec, small)
        out["ok"] = bool(jnp.allclose(moved["w"], x)
                         and jnp.allclose(moved["b"], 1.0))
        out["ndev"] = len(moved["w"].sharding.device_set)
    """)
    assert out["ok"] and out["ndev"] == 4


def test_crosspod_ef_int8_grad_sync():
    out = run_sub("""
        from repro.launch.mesh import make_test_mesh
        from repro.train.compression import ef_int8_psum
        from functools import partial
        from jax.sharding import PartitionSpec as P

        mesh = make_test_mesh((2, 4), ("pod", "data"))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        # per-pod gradients differ; EF-int8 pmean over "pod"
        gp = jnp.stack([g, g * 3.0])     # pod-major view
        fn = jax.shard_map(partial(ef_int8_psum, axis_name="pod"),
                           mesh=mesh,
                           in_specs=(P("pod", "data"), P("pod", "data")),
                           out_specs=(P("pod", "data"), P("pod", "data")))
        with jax.set_mesh(mesh):
            synced, err = fn(gp.reshape(16, 64), jnp.zeros((16, 64)))
        true_mean = np.asarray((g + 3*g) / 2.0)
        got = np.asarray(synced)[:8]
        rel = np.abs(got - true_mean).max() / np.abs(true_mean).max()
        out["rel_err"] = float(rel)
    """)
    assert out["rel_err"] < 0.02
