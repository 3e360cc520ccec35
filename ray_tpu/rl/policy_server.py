"""External-env policy serving: PolicyServer + PolicyClient.

Reference: rllib/env/policy_server_input.py:1 + policy_client.py — an
EXTERNAL simulator (a game server, a robot, a process the cluster
doesn't control) connects over HTTP, asks the current policy for
actions, and reports rewards; the collected episodes become training
batches. TPU-scaled: the server is a Serve deployment (riding the
framework's HTTP proxy + replica machinery instead of a bespoke
HTTPServer), the policy is an RLModule's pure forward, and
drain_samples() returns PPO-ready (obs, actions, logp, rewards, dones)
arrays the Learner/LearnerGroup consume unchanged.
"""

from __future__ import annotations

import json
import threading


class _PolicyDeploymentImpl:
    """The replica: holds module params, serves actions, buffers
    transitions per episode. Deployed via serve (one replica — the
    sample buffer is replica-local state)."""

    def __init__(self, module_blob: bytes, params_blob: bytes,
                 explore: bool = True, seed: int = 0):
        import jax

        from ray_tpu._private import serialization

        self.module = serialization.unpack_payload(
            json.loads(module_blob) if isinstance(module_blob, str)
            else module_blob)
        self.params = serialization.unpack_payload(params_blob)
        self.explore = explore
        self._key = jax.random.PRNGKey(seed)
        self._lock = threading.Lock()
        self._episodes: dict[str, dict] = {}
        self._complete: list[dict] = []
        self._next_eid = 0

    def __call__(self, req: dict):
        cmd = req.get("cmd")
        if cmd == "start_episode":
            with self._lock:
                eid = f"ep_{self._next_eid}"
                self._next_eid += 1
                self._episodes[eid] = {
                    "obs": [], "actions": [], "logp": [], "rewards": [],
                }
            return {"episode_id": eid}
        if cmd == "get_action":
            return self._get_action(req["episode_id"], req["obs"])
        if cmd == "log_returns":
            with self._lock:
                ep = self._episodes[req["episode_id"]]
                # reward for the MOST RECENT action (reference
                # log_returns contract)
                ep["rewards"][-1] += float(req["reward"])
            return {"ok": True}
        if cmd == "end_episode":
            with self._lock:
                ep = self._episodes.pop(req["episode_id"])
                ep["final_obs"] = req.get("obs")
                self._complete.append(ep)
            return {"ok": True}
        raise ValueError(f"unknown policy server cmd {cmd!r}")

    def _get_action(self, eid: str, obs):
        import jax
        import jax.numpy as jnp
        import numpy as np

        ob = jnp.asarray(np.asarray(obs, np.float32))[None, :]
        with self._lock:
            self._key, k = jax.random.split(self._key)
            params = self.params
        if self.explore:
            act, logp = self.module.forward_exploration(params, ob, k)
            a, lp = int(act[0]), float(logp[0])
        else:
            a = int(self.module.forward_inference(params, ob)[0])
            lp = 0.0
        with self._lock:
            ep = self._episodes[eid]
            ep["obs"].append([float(x) for x in np.asarray(obs).ravel()])
            ep["actions"].append(a)
            ep["logp"].append(lp)
            ep["rewards"].append(0.0)  # log_returns accumulates into it
        return {"action": a, "logp": lp}

    # -- trainer-side RPCs (via the deployment handle, not HTTP) --

    def set_weights(self, params_blob: bytes):
        from ray_tpu._private import serialization

        with self._lock:
            self.params = serialization.unpack_payload(params_blob)
        return True

    def drain_samples(self):
        """Completed episodes since the last drain, as plain lists."""
        with self._lock:
            out, self._complete = self._complete, []
        return out

    def stats(self):
        with self._lock:
            return {"open_episodes": len(self._episodes),
                    "complete_episodes": len(self._complete)}


class PolicyServer:
    """Driver-side facade: deploy the policy, push weights, drain
    training batches (reference PolicyServerInput's role)."""

    def __init__(self, module, params, *, name: str = "policy",
                 route: str = "/policy", explore: bool = True,
                 seed: int = 0):
        from ray_tpu import serve
        from ray_tpu._private import serialization
        from ray_tpu.serve.api import Deployment

        self.name = name
        dep = Deployment(_PolicyDeploymentImpl, max_concurrent_queries=16,
                         resources={"CPU": 0}, route_prefix=route)
        self.handle = serve.run(dep, name=name, init_args=(
            serialization.pack_payload(module),
            serialization.pack_payload(params),
        ), init_kwargs={"explore": explore, "seed": seed})
        self.address = serve.start_http_proxy()
        self.route = route

    def set_weights(self, params) -> None:
        import ray_tpu
        from ray_tpu._private import serialization

        ray_tpu.get(self.handle.method("set_weights").remote(
            serialization.pack_payload(params)), timeout=120)

    def drain_samples(self) -> dict | None:
        """PPO-ready arrays from all completed episodes since the last
        call: obs/actions/logp/rewards/dones (+ episode_returns)."""
        import numpy as np

        import ray_tpu

        eps = ray_tpu.get(
            self.handle.method("drain_samples").remote(), timeout=120)
        if not eps:
            return None
        obs, actions, logp, rewards, dones, rets = [], [], [], [], [], []
        for ep in eps:
            n = len(ep["actions"])
            if n == 0:
                continue
            obs.extend(ep["obs"])
            actions.extend(ep["actions"])
            logp.extend(ep["logp"])
            rewards.extend(ep["rewards"])
            dones.extend([False] * (n - 1) + [True])
            rets.append(sum(ep["rewards"]))
        if not actions:
            return None
        return {
            "obs": np.asarray(obs, np.float32),
            "actions": np.asarray(actions, np.int32),
            "logp": np.asarray(logp, np.float32),
            "rewards": np.asarray(rewards, np.float32),
            "dones": np.asarray(dones, bool),
            "episode_returns": rets,
        }


class PolicyClient:
    """The external simulator's side (reference policy_client.py): a
    plain HTTP client — no framework import needed beyond stdlib, so a
    third-party process can speak it from anywhere."""

    def __init__(self, address: tuple, route: str = "/policy",
                 timeout: float = 60.0):
        self.host, self.port = address
        self.route = route
        self.timeout = timeout

    def _post(self, body: dict) -> dict:
        import http.client

        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout)
        try:
            conn.request("POST", self.route, json.dumps(body),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            data = json.loads(r.read() or b"null")
            if r.status != 200:
                raise RuntimeError(f"policy server {r.status}: {data}")
            return data
        finally:
            conn.close()

    def start_episode(self) -> str:
        return self._post({"cmd": "start_episode"})["episode_id"]

    def get_action(self, episode_id: str, obs) -> int:
        import numpy as np

        return self._post({
            "cmd": "get_action", "episode_id": episode_id,
            "obs": [float(x) for x in np.asarray(obs).ravel()],
        })["action"]

    def log_returns(self, episode_id: str, reward: float) -> None:
        self._post({"cmd": "log_returns", "episode_id": episode_id,
                    "reward": float(reward)})

    def end_episode(self, episode_id: str, obs=None) -> None:
        import numpy as np

        self._post({
            "cmd": "end_episode", "episode_id": episode_id,
            "obs": ([float(x) for x in np.asarray(obs).ravel()]
                    if obs is not None else None),
        })
