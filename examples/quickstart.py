"""Quickstart: one Mix2FLD round, end to end, in under a minute on CPU.

Shows the whole pipeline of Algorithm 1:
  1. devices mix up seed samples (eq. 6) and upload them with their
     per-label average outputs (eq. 2) over the fading uplink,
  2. the server inversely mixes the seeds (eq. 7 / Prop. 1), builds
     G_out, and runs the output-to-model conversion (eq. 5),
  3. devices download the converted global model (FL-style downlink).

Seed collection (steps 1-2) is fully batched over the device axis and
runs the inverse-Mixup through the Pallas kernel — architecture and
D-scaling knobs are documented in docs/seed_pipeline.md.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.channel import ChannelConfig
from repro.core.protocols import FederatedConfig, FederatedTrainer
from repro.data import partition_iid, synthetic_images
from repro.launch.compile_cache import enable_compile_cache
from repro.models.cnn import CNN


def main():
    enable_compile_cache()
    key = jax.random.PRNGKey(0)
    x, y = synthetic_images(key, 3500)
    dev_x, dev_y = partition_iid(x[:2500], y[:2500], 5, 500, 10)
    test_x, test_y = jnp.asarray(x[2500:]), jnp.asarray(y[2500:])

    fc = FederatedConfig(protocol="mix2fld", num_devices=5, local_iters=60,
                         local_batch=32, server_iters=60, max_rounds=2)
    ch = ChannelConfig(num_devices=5)  # paper's asymmetric 23/40 dBm
    trainer = FederatedTrainer(CNN(), fc, ch)
    h = trainer.run(dev_x, dev_y, test_x, test_y, log=print)

    meta = h["seeds"]  # lightweight metadata; arrays via keep_seed_arrays
    print(f"\nuploaded mixed-up seeds : {meta['n_uploaded']}")
    print(f"inversely mixed-up seeds: {meta['n_train']} "
          f"(augmented, hard labels={meta['hard_labels']})")
    print(f"label-cycle histogram   : {meta['cycle_hist']}")
    print(f"accuracy after {fc.max_rounds} rounds: {h['acc'][-1]:.3f}")


if __name__ == "__main__":
    main()
