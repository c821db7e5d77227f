"""DiDiC-partition-aware distributed GNN training — the paper's technique
as a first-class framework feature.

Partitions a graph with DiDiC, places each partition on one mesh
data-shard, trains a GCN whose message passing runs through the halo
exchange (the TPU analogue of the thesis's Shadow Construct), and reports
the collective-volume savings vs random placement.

Runs on fake devices:
    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python examples/distributed_gnn_training.py
"""

import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import metrics, partitioners
from repro.core.didic import DidicConfig, didic_partition
from repro.data.pipeline import gnn_features
from repro.distributed.halo import build_halo_program, make_partitioned_spmm
from repro.distributed.placement import build_layout, collective_bytes_estimate
from repro.graphs import datasets
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_replay_mesh
from repro.models import gnn
from repro.optim import adamw


def main() -> None:
    enable_compile_cache()
    n_shards = 4
    graph = datasets.load("gis", scale=0.003)
    print(graph.summary())
    d_feat, n_classes, d_hidden = 32, 4, 32

    # --- Partition with DiDiC vs random; compare halo volume.
    didic_parts, _ = didic_partition(graph, DidicConfig(k=n_shards, iterations=40), seed=0)
    rand_parts = partitioners.random_partition(graph.n_nodes, n_shards, seed=0)
    for name, parts in (("random", rand_parts), ("didic", didic_parts)):
        bytes_, ec = collective_bytes_estimate(graph, parts, d_feat=d_hidden)
        print(f"  placement/{name}: edge_cut={ec*100:5.1f}%  halo≈{bytes_/1e6:.2f} MB/step")

    # --- Build the partition-aware layout + halo program (DiDiC placement).
    layout = build_layout(graph, didic_parts, n_shards)
    prog = build_halo_program(graph, layout)
    mesh = make_replay_mesh(n_shards)
    spmm = make_partitioned_spmm(prog, mesh, ("data",))
    print(f"  halo program: block={prog.block} B_max={prog.b_max} G_max={prog.g_max} "
          f"collective={prog.halo_bytes(d_hidden)/1e6:.2f} MB/step")

    # --- Features/labels in the partitioned layout; train a 2-layer GCN
    # whose aggregation IS the halo-exchange SpMM.
    x_host, labels_host = gnn_features(graph.n_nodes, d_feat, n_classes, seed=0)
    xp = layout.scatter_features(x_host)
    yp = layout.scatter_features(labels_host.astype(np.int32), fill=-1)
    shard = NamedSharding(mesh, P("data", None))
    x = jax.device_put(jnp.asarray(xp), shard)
    y = jax.device_put(jnp.asarray(yp), NamedSharding(mesh, P("data")))
    mask = (y >= 0).astype(jnp.float32)
    y = jnp.maximum(y, 0)

    cfg = gnn.GnnConfig(kind="gcn", d_in=d_feat, d_hidden=d_hidden, d_out=n_classes)
    params = gnn.gcn_init(cfg, jax.random.PRNGKey(0))
    opt_state = adamw.init(params)
    opt_cfg = adamw.AdamWConfig(lr=5e-3, warmup_steps=5, total_steps=60, weight_decay=0.0)

    def forward(p, x):
        h = x
        for i in range(cfg.n_layers):
            h = h @ p[f"w{i}"]
            h = spmm(h) + h  # halo-exchange aggregation + self loop
            if i < cfg.n_layers - 1:
                h = jax.nn.relu(h)
        return h

    def loss_fn(p):
        logits = forward(p, x)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    @jax.jit
    def train_step(p, s):
        loss, grads = jax.value_and_grad(loss_fn)(p)
        p, s, _ = adamw.update(p, grads, s, opt_cfg)
        return p, s, loss

    for step in range(60):
        params, opt_state, loss = train_step(params, opt_state)
        if step % 15 == 0 or step == 59:
            logits = forward(params, x)
            acc = float(((jnp.argmax(logits, -1) == y) * mask).sum() / mask.sum())
            print(f"  step {step:3d}: loss={float(loss):.4f} acc={acc:.3f}")

    print("\nDistributed GCN trained over DiDiC-placed shards with halo exchange.")


if __name__ == "__main__":
    main()
