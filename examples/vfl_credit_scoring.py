"""Vertical federated credit scoring: the full protocol flow on a device mesh.

Two parties (bank = active with labels, fintech = passive) hold disjoint
feature columns of the same customers. The forest builder runs under
shard_map with the party axis = mesh "model" axis; the message ledger
reconciles the bytes each collective *actually* ships against the predicted
wire model (and prices the paper-world Paillier protocol alongside); the
secure-aggregation simulation demonstrates the masking algebra on the
gradient broadcast.  The quantized transport (DESIGN.md §5) demonstrates
the compression subsystem end to end: same AUC to ~1e-4, ~5x fewer
histogram bytes.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/vfl_credit_scoring.py
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import boosting, metrics
from repro.core.types import TreeConfig
from repro.data import synthetic, tabular
from repro.federation import compress, secure, vfl
from repro.launch.mesh import make_mesh

if len(jax.devices()) < 2:
    raise SystemExit(
        "need >=2 devices: run with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )

PARTIES = 2
ds = synthetic.load("default_credit_card", n=8_000)
x_train, d_pad = tabular.pad_features(ds.x_train, PARTIES)
x_test, _ = tabular.pad_features(ds.x_test, PARTIES)
part = tabular.even_partition(d_pad, PARTIES)
print(f"bank (active) holds columns {part.columns(0)}, "
      f"fintech (passive) holds {part.columns(1)}")

# --- secure aggregation demo: parties mask their contributions; only the
# sum is visible to the aggregator (masks cancel exactly).
contrib = jnp.stack([jnp.ones(5) * 2.0, jnp.ones(5) * 3.0])
masks = secure.pairwise_masks(seed=42, num_parties=2, shape=(5,))
masked = secure.mask(contrib, masks)
print("masked party messages (unreadable):", np.asarray(masked[0][:3]))
print("aggregate (masks cancel):", np.asarray(secure.aggregate(masked)[:3]))

# --- federated training: lossless modes + the quantized transport
mesh = make_mesh((len(jax.devices()) // PARTIES, PARTIES), ("data", "model"))
tree_cfg = TreeConfig(max_depth=3, num_bins=32)
cfg = boosting.dynamic_fedgbf_config(rounds=8, tree=tree_cfg)

for aggregation, transport, subtraction in (
    ("histogram", None, False),         # paper-faithful full-histogram exchange
    ("argmax", None, False),            # beyond-paper candidate-only exchange
    ("histogram", compress.Q8, False),  # quantized exchange (DESIGN.md §5)
    ("histogram", compress.Q8, True),   # + sibling subtraction (DESIGN.md §6)
):
    run_tree = dataclasses.replace(tree_cfg, hist_subtraction=subtraction)
    run_cfg = dataclasses.replace(cfg, tree=run_tree)
    backend = vfl.make_vfl_backend(
        mesh, run_tree, aggregation=aggregation, transport=transport
    )
    model, _ = boosting.train_fedgbf(
        jnp.asarray(x_train), jnp.asarray(ds.y_train), run_cfg,
        jax.random.PRNGKey(0), backend=backend,
    )
    rep = metrics.classification_report(
        jnp.asarray(ds.y_test), boosting.predict(model, jnp.asarray(x_test))
    )
    # Measured bytes: every collective in the backend reports its actual
    # payload; the ledger reconciles them against the predicted wire model.
    ledger = compress.reconciled_ledger(
        mesh, run_tree, run_cfg, aggregation=aggregation, transport=transport,
        n_samples=x_train.shape[0], num_features=d_pad,
    )
    rec = ledger.reconcile()
    paillier = ledger.predicted_paillier()
    tag = (f"{aggregation}" + (f"-{transport.tag}" if transport else "")
           + ("+sub" if subtraction else ""))
    print(f"[{tag:17s}] test auc={rep['auc']:.4f} "
          f"wire measured={rec['total']['measured']/1e6:.1f} MB "
          f"predicted={rec['total']['predicted']/1e6:.1f} MB "
          f"(match={rec['total']['match']}, "
          f"histograms {rec['histograms']['measured']/1e6:.1f} MB) "
          f"paillier-model={paillier.total/1e6:.1f} MB")
print("-> same AUC at ~5x fewer histogram bytes under q8 (~9x with sibling "
      "subtraction on top); measured wire bytes reconcile exactly with the "
      "ledger's prediction")
