"""Fault-tolerant training demo: kill a node mid-run, restart, verify
bitwise-identical convergence.

Wires the REAL stack together: sharded train step + async checkpointer +
heartbeat supervisor + deterministic step-keyed data. A node failure is
injected mid-training; the supervisor detects the missed heartbeats, rolls
back to the last committed checkpoint, and replays — and because the data
pipeline is step-keyed, the replayed run produces exactly the losses the
uninterrupted run would have.

    PYTHONPATH=src python examples/fault_tolerant_train.py
"""

import tempfile

import numpy as np

import jax

from repro.checkpoint import restore, save
from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.data import DataConfig, SyntheticTokens
from repro.launch.steps import make_train_step
from repro.launch.train import make_local_mesh
from repro.models import init_params
from repro.optim import AdamWConfig, adamw_init
from repro.parallel.mesh_view import build_mesh_context
from repro.runtime import HeartbeatRegistry, TrainingSupervisor

STEPS, BATCH, SEQ = 24, 4, 64


def main() -> None:
    cfg = get_config("deepseek-7b").reduced()
    mesh = make_local_mesh(cfg)
    ctx = build_mesh_context(mesh, cfg)
    shape = ShapeSpec("ft", SEQ, BATCH, "train", 1)
    step_fn = jax.jit(
        make_train_step(cfg, ctx, shape, AdamWConfig(learning_rate=1e-3)),
        donate_argnums=(0, 1),
    )
    data = SyntheticTokens(DataConfig(cfg.vocab_size, SEQ, BATCH, seed=0))

    def fresh_state():
        params = init_params(cfg, jax.random.PRNGKey(0))
        return params, adamw_init(params)

    def train(with_failure: bool, ckpt_dir: str):
        reg = HeartbeatRegistry(num_nodes=2, deadline=1.0)

        def save_fn(step, state):
            save(ckpt_dir, step, state)

        def restore_fn():
            (params, opt), step = restore(ckpt_dir, fresh_state())
            return (params, opt), step

        losses = {}

        def one_step(state, step):
            params, opt = state
            batch = {k: jax.numpy.asarray(v) for k, v in data.batch(step).items()}
            params, opt, metrics = step_fn(params, opt, batch)
            losses[step] = float(metrics["loss"])
            return params, opt

        fired = []

        def injector(step):
            if with_failure and step == 13 and not fired:
                fired.append(step)
                print("  !! node 1 stops heartbeating at step 13")
                return 1
            return None

        sup = TrainingSupervisor(reg, save_fn, restore_fn, checkpoint_every=8)
        with ctx.mesh:
            sup.run(fresh_state(), one_step, steps=STEPS,
                    failure_injector=injector if with_failure else None)
        return losses, sup.restarts

    with tempfile.TemporaryDirectory() as d1, tempfile.TemporaryDirectory() as d2:
        print("clean run...")
        clean, r0 = train(False, d1)
        print("run with injected failure...")
        failed, r1 = train(True, d2)

    print(f"\nrestarts: clean={r0}, failure-run={r1}")
    diffs = [s for s in clean if abs(clean[s] - failed[s]) > 1e-6]
    print(f"loss trajectory: {len(clean)} steps, {len(diffs)} diverging steps")
    print(f"final loss: clean {clean[STEPS-1]:.5f} vs recovered {failed[STEPS-1]:.5f}")
    assert r1 >= 1 and not diffs, "recovery must replay to identical losses"
    print("OK — failure recovered with bitwise-identical training trajectory")


if __name__ == "__main__":
    main()
