"""EnvManager (paper §6.1): a lightweight controller that drives ONE
environment's lifecycle — reset, then an independent loop alternating
LLMProxy generation with env.step — assembling a token-aligned multi-turn
trajectory. Each EnvManager runs on its own timeline, so a slow or failed
environment never blocks the others (R2).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable, Dict, List, Optional

from repro.core.proxy import LLMProxy
from repro.data.pipeline import Trajectory
from repro.data.tokenizer import ByteTokenizer
from repro.envs.base import EnvError, TextEnv
from repro.obs import spans
from repro.rl.engine import GenRequest, GenResult

_ids = itertools.count()


def em_counter_value() -> int:
    """Current value of the global EnvManager id counter (non-consuming;
    peek-then-recreate). Captured into rollout snapshots so a restore in a
    FRESH process can advance the counter past every snapshotted id —
    otherwise new managers could reuse an already-consumed ``traj_id`` and
    be wrongly dropped by the SampleBuffer dedup filter."""
    global _ids
    v = next(_ids)
    _ids = itertools.count(v)
    return v


def ensure_em_counter(minimum: int):
    """Advance the global id counter so future ids are >= ``minimum``."""
    global _ids
    v = next(_ids)
    _ids = itertools.count(max(v, minimum))


class EMState(Enum):
    IDLE = 0
    GENERATING = 1
    DONE = 2
    FAILED = 3
    ABORTED = 4


@dataclass
class RolloutPolicy:
    max_new_tokens: int = 48
    temperature: float = 1.0
    max_prompt_tokens: int = 384
    stop_tokens: tuple = (2,)       # EOS


class EnvManager:
    def __init__(self, env: TextEnv, proxy: LLMProxy,
                 tokenizer: Optional[ByteTokenizer] = None,
                 policy: Optional[RolloutPolicy] = None,
                 tag: Optional[str] = None,
                 on_complete: Optional[Callable[["EnvManager"], None]] = None,
                 group_id: str = "",
                 on_tokens: Optional[Callable] = None):
        self.em_id = f"em-{next(_ids)}"
        self.env = env
        self.proxy = proxy
        self.tok = tokenizer or ByteTokenizer()
        self.policy = policy or RolloutPolicy()
        self.tag = tag or env.TASK
        self.on_complete = on_complete
        # incremental token-stream subscriber, forwarded with every
        # generation request (see LLMProxy.submit / repro.serve.stream)
        self.on_tokens = on_tokens
        self.group_id = group_id
        self.state = EMState.IDLE
        self.tokens: List[int] = []
        self.loss_mask: List[int] = []
        self.logprobs: List[float] = []
        self.turns = 0
        self.start_version = 0
        self.end_version = 0
        self.env_return = 0.0
        # tokens the aborted in-flight request had generated, which the
        # trajectory never took in (rollout waste accounting)
        self.dropped_tokens = 0
        self._req_counter = itertools.count()
        self._active_req: Optional[str] = None

    # ------------------------------------------------------------------
    def start(self, version: int, seed: Optional[int] = None):
        """reset + first generation request."""
        self.start_version = version
        try:
            obs = self.env.reset(seed=seed)
        except EnvError:
            self.state = EMState.FAILED
            if self.on_complete:
                self.on_complete(self)
            return
        self._append_obs(obs)
        self._request_action()

    def _append_obs(self, obs: str):
        ids = self.tok.encode(obs + "\n", bos=not self.tokens)
        self.tokens.extend(ids)
        self.loss_mask.extend([0] * len(ids))
        self.logprobs.extend([0.0] * len(ids))

    def _prompt(self) -> List[int]:
        return self.tokens[-self.policy.max_prompt_tokens:]

    def _request_action(self):
        self.state = EMState.GENERATING
        rid = f"{self.em_id}.r{next(self._req_counter)}"
        self._active_req = rid
        self.proxy.submit(
            GenRequest(request_id=rid, prompt=self._prompt(),
                       max_new_tokens=self.policy.max_new_tokens,
                       temperature=self.policy.temperature,
                       stop_tokens=self.policy.stop_tokens, tag=self.tag),
            callback=self.on_generation, on_tokens=self.on_tokens)

    # ------------------------------------------------------------------
    def on_generation(self, result: GenResult):
        """Proxy callback: apply the action to the environment."""
        self._active_req = None
        if self.state in (EMState.ABORTED, EMState.DONE, EMState.FAILED):
            return
        if result.finish_reason == "aborted":
            self.state = EMState.ABORTED
            self.dropped_tokens = len(result.tokens)
            if self.on_complete:
                self.on_complete(self)
            return
        action_ids = [t for t in result.tokens
                      if t not in self.policy.stop_tokens]
        self.tokens.extend(action_ids)
        self.loss_mask.extend([1] * len(action_ids))
        self.logprobs.extend(result.logprobs[: len(action_ids)])
        self.end_version = result.weight_version
        action = self.tok.decode(action_ids)
        self.turns += 1
        try:
            with spans.span("repro.env.step"):
                obs, reward, done, _ = self.env.step(action)
        except EnvError:
            self.state = EMState.FAILED
            if self.on_complete:
                self.on_complete(self)
            return
        self.env_return += reward
        if done:
            self.state = EMState.DONE
            if self.on_complete:
                self.on_complete(self)
            return
        self._append_obs(obs)
        self._request_action()

    # ------------------------------------------------------------------
    def abort(self):
        """Cancel this trajectory (staleness bound / redundant rollouts).

        Idempotent. A GENERATING manager is cancelled through the proxy
        and completes via the aborted-result callback; a manager that is
        not generating (IDLE, or mid-transition) is completed HERE —
        ``on_complete`` must still fire, otherwise the runner never learns
        the manager terminated and leaks it in its active set forever.
        """
        if self.state in (EMState.DONE, EMState.FAILED, EMState.ABORTED):
            return                       # already completed; hook already ran
        if self.state == EMState.GENERATING and self._active_req:
            self.proxy.abort(self._active_req)
            return
        self.state = EMState.ABORTED
        if self.on_complete:
            self.on_complete(self)

    def fail(self, reason: str = "injected"):
        """Mark this manager FAILED (environment crash, engine loss, or an
        injected fault — paper §8: env failures ~1/10 iterations).
        Idempotent like :meth:`abort`; an in-flight generation request is
        cancelled through the proxy, and its eventual aborted-result
        callback early-outs on the FAILED state."""
        if self.state in (EMState.DONE, EMState.FAILED, EMState.ABORTED):
            return
        rid = self._active_req
        self.state = EMState.FAILED
        if rid is not None:
            self.proxy.abort(rid)
        if self.on_complete:
            self.on_complete(self)

    def retry(self):
        """Re-issue the in-flight generation request after its engine was
        lost and no snapshot covers it: the trajectory's token prefix is
        intact on this side, so a fresh request (new id, re-prefill)
        resumes it from the last completed turn."""
        if self.state != EMState.GENERATING:
            return
        self._request_action()

    # ------------------------------------------------------------------
    # rollout-level checkpointing (repro.ft.snapshot)
    # ------------------------------------------------------------------
    def snapshot_state(self) -> Dict:
        """Serializable record of this manager's full state machine: token
        stream, env object (picklable: plain fields + ``random.Random``),
        versions, request counter, and the id of the in-flight request (its
        engine-side KV state is captured separately)."""
        nxt = next(self._req_counter)       # peek-then-recreate: capture
        self._req_counter = itertools.count(nxt)    # must not perturb ids
        return {
            "em_id": self.em_id, "tag": self.tag,
            "group_id": self.group_id, "state": self.state.name,
            "tokens": list(self.tokens), "loss_mask": list(self.loss_mask),
            "logprobs": list(self.logprobs), "turns": self.turns,
            "start_version": self.start_version,
            "end_version": self.end_version,
            "env_return": self.env_return,
            "req_counter": nxt,
            "active_req": self._active_req,
            "env": self.env,
        }

    @classmethod
    def restore_from(cls, rec: Dict, proxy: LLMProxy,
                     tokenizer: Optional[ByteTokenizer] = None,
                     policy: Optional[RolloutPolicy] = None,
                     on_complete: Optional[Callable] = None,
                     ) -> "EnvManager":
        """Rebuild a manager from ``snapshot_state`` output. The restored
        manager keeps its original ``em_id`` (so its trajectory dedups
        against a pre-crash completion) and its request counter (so a
        resumed request id matches the snapshotted engine-side state). The
        caller resumes generation via the proxy (reinject / submit) —
        ``restore_from`` itself issues no requests."""
        em = cls(rec["env"], proxy, tokenizer=tokenizer, policy=policy,
                 tag=rec["tag"], on_complete=on_complete,
                 group_id=rec["group_id"])
        em.em_id = rec["em_id"]
        em.state = EMState[rec["state"]]
        em.tokens = list(rec["tokens"])
        em.loss_mask = list(rec["loss_mask"])
        em.logprobs = list(rec["logprobs"])
        em.turns = rec["turns"]
        em.start_version = rec["start_version"]
        em.end_version = rec["end_version"]
        em.env_return = rec["env_return"]
        em._req_counter = itertools.count(rec["req_counter"])
        em._active_req = rec["active_req"]
        return em

    def trajectory(self) -> Trajectory:
        return Trajectory(
            traj_id=self.em_id, task=self.env.TASK,
            tokens=list(self.tokens), loss_mask=list(self.loss_mask),
            logprobs=list(self.logprobs),
            reward=self.env_return, group_id=self.group_id,
            start_version=self.start_version, version=self.end_version,
            turns=self.turns,
            meta={"state": self.state.name})
