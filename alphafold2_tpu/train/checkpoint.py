"""Checkpoint/resume via orbax — a subsystem the reference lacks entirely
(no torch.save/state_dict anywhere, SURVEY.md S5.4)."""

from __future__ import annotations

import os
from typing import Optional, Tuple

import orbax.checkpoint as ocp


class CheckpointManager:
    """Thin wrapper: save(step, state) / maybe_restore(template) -> (state, step)."""

    def __init__(self, directory: str, keep: int = 3):
        self._dir = os.path.abspath(directory)
        os.makedirs(self._dir, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=keep, create=True, enable_async_checkpointing=True
            ),
        )

    def save(self, step: int, state) -> None:
        self._mgr.save(step, args=ocp.args.StandardSave(state))

    def maybe_restore(self, template) -> Tuple[object, int]:
        latest = self._mgr.latest_step()
        if latest is None:
            return template, 0
        restored = self._mgr.restore(
            latest, args=ocp.args.StandardRestore(template)
        )
        return restored, latest

    def restore_params(self, params_template):
        """Restore ONLY the model parameters from the latest checkpoint.

        Inference doesn't need (and must not depend on) the optimizer
        state — its tree shape varies with training config (e.g.
        optax.MultiSteps wrapping under gradient accumulation). Partial
        restore matches just the ``params`` subtree. ``params_template``
        may be abstract (jax.eval_shape output).
        """
        latest = self._mgr.latest_step()
        if latest is None:
            raise FileNotFoundError(f"no checkpoint found under {self._dir!r}")
        restored = self._mgr.restore(
            latest,
            args=ocp.args.PyTreeRestore(
                item={"params": params_template}, partial_restore=True
            ),
        )
        return restored["params"], latest

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()
