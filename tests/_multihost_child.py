"""Child process for the real 2-process multi-host test
(tests/test_multihost.py). Each process owns 4 virtual CPU devices; the
pair forms one 8-device (4dp x 2sp) pod. Prints the step loss for the
parent to compare across ranks."""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    pid = int(sys.argv[1])
    port = sys.argv[2]

    from alphafold2_tpu.parallel.distributed import (
        global_batch,
        initialize,
        pod_mesh,
    )

    ok = initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
    assert ok, "distributed init did not run"
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()
    assert jax.local_device_count() == 4

    from alphafold2_tpu.config import Config, DataConfig, MeshConfig, ModelConfig, TrainConfig
    from alphafold2_tpu.data.pipeline import SyntheticDataset
    from alphafold2_tpu.train.loop import build_model, init_state, make_train_step

    cfg = Config(
        model=ModelConfig(dim=32, depth=1, heads=2, dim_head=16,
                          max_seq_len=32, bfloat16=False),
        mesh=MeshConfig(data_parallel=4, seq_parallel=2),
        data=DataConfig(crop_len=8, msa_depth=2, msa_len=8, batch_size=2,
                        min_len_filter=8),  # LOCAL batch; global = 4
        train=TrainConfig(gradient_accumulate_every=1, warmup_steps=2,
                          seed=0),
    )
    # each host feeds a DIFFERENT slice of the global batch
    local_batch = next(iter(SyntheticDataset(cfg.data, seed=100 + pid)))

    mesh = pod_mesh(cfg.mesh.data_parallel, cfg.mesh.seq_parallel)
    model = build_model(cfg)
    state = init_state(cfg, model, local_batch)  # same seed -> same params
    step = make_train_step(model, mesh)
    gb = global_batch(local_batch, mesh)
    state, metrics = step(state, gb, jax.random.key(7))
    print(f"RANK {pid} LOSS {float(metrics['loss']):.6f} "
          f"GNORM {float(metrics['grad_norm']):.6f}", flush=True)


if __name__ == "__main__":
    main()
