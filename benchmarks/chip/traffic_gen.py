"""The one traffic generator: reads a mix's parameters (``traffic/<mix>.json``)
and makes its inputs from the seed.

* ``train``: trajectory super-batches in the trainer's wire format
  (``TrajectoryBatch`` fields), every row different.
* ``serve``: closed-loop env clients. Each client steps its own copy of the
  tabletop manipulation env, submits the observation, waits for the
  action, then sleeps a lognormal physics-step time.

The env and the lognormal step-time generator are copies of
``repro.envs.toy_manipulation`` (``ManipulationEnv``, ``lognormal_latency``)
so that the load the benchmark offers cannot change with the program.
"""
from __future__ import annotations

import zlib
from typing import Callable, Dict, Optional

import numpy as np

SUITES = ("spatial", "object", "goal", "long")
T_OBS = 12
GRID = 8
FRAME_DIM = GRID * GRID * 3
TASKS_PER_SUITE = 10


def seed32(seed: int, stream: int = 0) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` from any whole number.
    (PRNGKey keeps only the low 32 bits of a Python int.)"""
    return int(np.random.SeedSequence([seed, stream]).generate_state(
        1, np.uint32)[0]) & 0x7FFFFFFF


# ---------------------------------------------------------------------------
# training batches
# ---------------------------------------------------------------------------

def train_batch(rng: np.random.Generator, mix: Dict, config: Dict) -> Dict:
    """One super-batch of ``segments`` segments of ``horizon`` env steps
    (+1 bootstrap observation), as rollout workers emit them: an episode
    ends inside a segment with probability ``episode_end_prob``, with a
    success reward, a natural termination and the mask cleared after it."""
    b, t = mix["segments"], mix["horizon"]
    ph = config["policy_head"]
    a, va = ph["action_dim"], ph["action_vocab_size"]
    tp1 = t + 1
    obs = rng.integers(0, config["vocab_size"], (b, tp1, mix["instruction_tokens"]))
    actions = rng.integers(0, va, (b, tp1, a))
    actions[:, t] = 0
    logp = -np.log(va) + mix["behavior_logp_std"] * rng.standard_normal(
        (b, tp1, a))
    logp[:, t] = 0.0
    rewards = np.zeros((b, t))
    dones = np.zeros((b, t))
    mask = np.ones((b, t))
    ends = rng.uniform(size=b) < mix["episode_end_prob"]
    at = rng.integers(0, t, b)
    for i in np.flatnonzero(ends):
        rewards[i, at[i]] = 1.0
        dones[i, at[i]] = 1.0
        mask[i, at[i] + 1:] = 0.0
    start = rng.integers(0, mix["max_episode_steps"] - tp1, b)
    frames = np.zeros((b, tp1, ph["num_prefix_tokens"], ph["frontend_dim"]))
    frames[..., :FRAME_DIM] = rng.uniform(size=(b, tp1, ph["num_prefix_tokens"],
                                                FRAME_DIM))
    return {
        "obs_tokens": obs.astype(np.int32),
        "actions": actions.astype(np.int32),
        "behavior_logp": logp.astype(np.float32),
        "behavior_value": rng.standard_normal((b, tp1)).astype(np.float32),
        "rewards": rewards.astype(np.float32),
        "dones": dones.astype(np.float32),
        "steps": (start[:, None] + np.arange(tp1)).astype(np.int32),
        "mask": mask.astype(np.float32),
        "policy_version": np.zeros((b,), np.int32),
        "prefix_embeds": frames.astype(np.float32),
    }


# ---------------------------------------------------------------------------
# serving: env clients (copied from repro.envs.toy_manipulation)
# ---------------------------------------------------------------------------

def lognormal_latency(mean_ms: float, sigma: float,
                      rng: np.random.Generator) -> Callable[[], float]:
    """Long-tailed physics-step time in seconds; ``mean_ms`` is the median
    (the location parameter), as in the original generator."""
    mu = np.log(mean_ms / 1000.0)
    return lambda: float(rng.lognormal(mu, sigma))


def _render(agent, obj, goal, obj2=None, goal2=None) -> np.ndarray:
    img = np.zeros((GRID, GRID, 3), np.float32)

    def blob(p):
        i = int(np.clip(p[1] * GRID, 0, GRID - 1))
        j = int(np.clip(p[0] * GRID, 0, GRID - 1))
        return i, j
    for ch, p in ((0, agent), (1, obj), (2, goal)):
        img[blob(p) + (ch,)] = 1.0
    if obj2 is not None:
        img[blob(obj2) + (1,)] = 0.5
        img[blob(goal2) + (2,)] = 0.5
    return img.reshape(-1)


class Env:
    """One tabletop instance: move, grip an object, deliver it to a goal."""

    def __init__(self, suite: str, max_steps: int, action_vocab: int,
                 action_dim: int, rng: np.random.Generator):
        assert suite in SUITES, suite
        self.suite, self.max_steps = suite, max_steps
        self.action_vocab, self.action_dim = action_vocab, action_dim
        self.rng = rng
        self.tol = 0.22
        self.task_id = 0
        self.reset(0)

    def _layout(self, task_id: int):
        seed = zlib.crc32(f"{self.suite}/{task_id}".encode()) % (2 ** 31)
        r = np.random.default_rng(seed)
        agent, obj, goal = (np.array([0.5, 0.5]), np.array([0.25, 0.25]),
                            np.array([0.75, 0.75]))

        def apart(anchor):
            for _ in range(100):
                p = r.uniform(0.15, 0.85, 2)
                if np.linalg.norm(p - anchor) >= 1.5 * self.tol:
                    return p
            return p
        if self.suite == "spatial":
            goal = apart(obj)
        elif self.suite == "object":
            obj = apart(goal)
        elif self.suite == "goal":
            obj = r.uniform(0.15, 0.85, 2)
            goal = apart(obj)
        obj2 = goal2 = None
        if self.suite == "long":
            obj = r.uniform(0.15, 0.85, 2)
            goal = apart(obj)
            obj2 = r.uniform(0.15, 0.85, 2)
            goal2 = apart(obj2)
        return agent, obj, goal, obj2, goal2

    def reset(self, task_id: Optional[int] = None) -> Dict:
        if task_id is not None:
            self.task_id = task_id
        (self.agent, self.obj, self.goal,
         self.obj2, self.goal2) = self._layout(self.task_id)
        self.holding, self.delivered, self.t = 0, 0, 0
        return self._obs()

    def _obs(self) -> Dict:
        toks = np.zeros(T_OBS, np.int32)
        toks[0] = SUITES.index(self.suite) + 1
        toks[1] = 10 + (self.task_id % TASKS_PER_SUITE)
        toks[2] = 30 + self.delivered
        if self.suite == "long" and self.delivered >= 1:
            frame = _render(self.agent, self.obj2, self.goal2)
        else:
            frame = _render(self.agent, self.obj, self.goal, self.obj2,
                            self.goal2)
        return {"tokens": toks, "frame": frame, "step": self.t}

    def _target(self):
        if self.suite == "long" and self.delivered >= 1:
            return self.obj2, self.goal2
        return self.obj, self.goal

    def step(self, action_tokens) -> tuple:
        a = (np.asarray(action_tokens, np.float64)
             / (self.action_vocab - 1)) * 2.0 - 1.0
        obj, _ = self._target()
        self.agent = np.clip(self.agent + 0.18 * a[:2], 0, 1)
        grip = a[2] > 0
        if grip and np.linalg.norm(self.agent - obj) < self.tol:
            self.holding = 2 if (self.suite == "long"
                                 and self.delivered >= 1) else 1
        if not grip:
            self.holding = 0
        if self.holding == 1:
            self.obj = self.agent.copy()
        elif self.holding == 2:
            self.obj2 = self.agent.copy()
        obj, goal = self._target()
        done = False
        if np.linalg.norm(obj - goal) < self.tol:
            if self.suite == "long" and self.delivered == 0:
                self.delivered, self.holding = 1, 0
            else:
                done = True
        self.t += 1
        done = done or self.t >= self.max_steps
        return self._obs(), done
