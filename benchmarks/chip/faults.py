"""Faults planted under the timed path, to show that the check catches
them: each is installed in the system under test before a run and
removed after it.

- ``unchanged``: the train step returns the state it was given;
- ``half``: the train step leaves out half of the batch's rows and takes
  the mean over the rest;
- ``token``: the engine alters every third sampled token where it
  produces it (its logprob stays the original token's);
- ``stale``: the engine's weight sync takes the new version's number but
  keeps the weights it had.

The exchange between chips is a fault only a cell on several chips can
have; no cell here has one.
"""
from __future__ import annotations

from typing import Callable

FAULTS = ("unchanged", "half", "token", "stale")


def _broken_step(fault: str, step):
    if fault == "unchanged":
        def broken(state, batch):
            new, metrics = step(state, batch)
            return new._replace(params=state.params, opt=state.opt), metrics
    else:
        def broken(state, batch):
            n = batch["loss_mask"].shape[0] // 2
            mask = batch["loss_mask"].at[n:].set(0)
            return step(state, dict(batch, loss_mask=mask))
    return broken


def plant(fault: str, vocab: int) -> Callable[[], None]:
    """Install ``fault``; returns the function that removes it."""
    from repro.rl import trainer
    from repro.rl.engine import InferenceEngine
    if fault in ("unchanged", "half"):
        real = trainer.make_grpo_train_step

        def make(model, opt, **kw):
            return _broken_step(fault, real(model, opt, **kw))
        trainer.make_grpo_train_step = make
        return lambda: setattr(trainer, "make_grpo_train_step", real)
    if fault == "token":
        real_append = InferenceEngine._append_token

        def altered(self, i, tok, lp):
            return real_append(self, i, (tok + 1) % vocab if tok % 3 == 0
                               else tok, lp)
        InferenceEngine._append_token = altered
        return lambda: setattr(InferenceEngine, "_append_token",
                               real_append)
    if fault == "stale":
        real_update = InferenceEngine.update_params

        def stale(self, params, version, recompute_caches=True):
            return real_update(self, self.params, version, recompute_caches)
        InferenceEngine.update_params = stale
        return lambda: setattr(InferenceEngine, "update_params", real_update)
    raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
