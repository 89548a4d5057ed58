"""Pod-scale distributed search demo on fake devices.

MUST run as its own process (device count is locked at first jax import):
    PYTHONPATH=src python examples/distributed_search.py
It runs on eight virtual CPU devices and leaves an attached TPU alone;
``chip_smoke.py --chips 4`` drives the same sharded search on real chips.
"""
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax                  # noqa: E402
import jax.numpy as jnp     # noqa: E402
import numpy as np          # noqa: E402

from repro.core.build import build_graph                      # noqa: E402
from repro.core.distributed import make_distributed_search    # noqa: E402
from repro.core.search import brute_force_topk, recall_at_k   # noqa: E402
from repro.core.types import SearchParams                     # noqa: E402
from repro.launch.mesh import make_test_mesh                  # noqa: E402


def main():
    mesh = make_test_mesh((4, 2), ("data", "model"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    sp = SearchParams(k=10, pool=64, max_iters=96)
    step = make_distributed_search(mesh, sp, data_axes=("data",),
                                   query_axis="model")

    N, D, R, S = 8000, 32, 16, 4
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(N, D)).astype(np.float32)
    print(f"building {S} per-shard subgraphs ({N // S} vectors each)...")
    parts = [build_graph(vecs[i * N // S:(i + 1) * N // S], R)
             for i in range(S)]
    idx = {
        "vectors": np.concatenate([np.asarray(g.vectors) for g in parts]),
        "nbrs": np.concatenate([np.asarray(g.nbrs) for g in parts]),
        "alive": np.concatenate([np.asarray(g.alive) for g in parts]),
        "e_in": np.concatenate([np.asarray(g.e_in) for g in parts]),
        "cache_vectors": np.zeros((S * 256, D), np.float32),
        "slot_hid": np.full((S * 256,), -1, np.int32),
        "h2d": np.full((N,), -1, np.int32),
        "f_recent": np.zeros((N,), np.float32),
    }
    Q = rng.normal(size=(64, D)).astype(np.float32)
    with jax.set_mesh(mesh):
        jidx = {k: jnp.asarray(v) for k, v in idx.items()}
        ids, dists = jax.jit(step)(jidx, jnp.asarray(Q), jax.random.PRNGKey(0))
        ids.block_until_ready()
    truth, _ = brute_force_topk(build_graph(vecs, R), jnp.asarray(Q), 10)
    print("distributed recall@10:",
          float(recall_at_k(jnp.asarray(np.asarray(ids)), truth)))


if __name__ == "__main__":
    main()
