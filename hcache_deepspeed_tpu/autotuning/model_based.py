"""Model-based autotuning: prune with compile-time estimates, then
explore the remaining space with a learned cost model instead of timing
every candidate.

Reference analogs (``/root/reference/deepspeed/autotuning/``):
* ``autotuner.py`` — memory-estimate pruning of micro-batch sizes
  before any experiment runs, staged experiment flow, and the
  ``ds_config_optimal.json`` artifact.
* ``tuner/model_based_tuner.py`` — XGBoost cost model over flattened
  config features: random init trials, predict-the-rest, measure the
  top prediction, refit (INIT_NUM=2, 0.2 random exploration).
* ``scheduler.py`` — resumable experiment state on disk.

TPU re-design: the expensive reference machinery (cluster relaunch per
experiment, xgboost) dissolves into two XLA facilities —
* **OOM prediction is exact, not modeled**: ``jit(...).lower().compile()
  .memory_analysis()`` returns the partitioned program's true peak HBM
  (args + temps); candidates over the budget are pruned without a
  single timed step (the reference must estimate activation memory by
  formula: ``autotuner.py _get_plausible_mbs``).
* **The cost model's prior is the roofline**: XLA ``cost_analysis()``
  flops + memory_analysis bytes give ``t >= max(flops/peak,
  bytes/bandwidth)`` per candidate; a least-squares correction over
  measured trials (features: config numerics + the roofline estimate)
  replaces xgboost — the estimate already carries the physics, so a
  linear residual model is enough to rank.
"""

import json
import math
import os
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from ..utils.logging import logger
from .autotuner import ExperimentResult

INIT_NUM = 2                      # reference model_based_tuner.py:16
RANDOM_EXPLORATION = 0.2          # reference model_based_tuner.py:56


def aot_estimate(jitted, *args, peak_flops: float = 0.0,
                 hbm_bytes_per_s: float = 0.0, **kwargs) -> Dict:
    """AOT-compile ``jitted`` for ``args`` and return
    ``{"peak_bytes", "flops", "time_est"}`` without executing it.
    Works on any backend (the CPU mesh gives the same partitioned
    program the chips would run)."""
    compiled = jitted.lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    peak_bytes = 0
    if mem is not None:
        peak_bytes = int(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0))
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0) or 0.0)
    bytes_accessed = float(cost.get("bytes accessed", 0.0) or 0.0)
    t_flops = flops / peak_flops if peak_flops else 0.0
    t_mem = bytes_accessed / hbm_bytes_per_s if hbm_bytes_per_s else 0.0
    return {"peak_bytes": peak_bytes, "flops": flops,
            "bytes_accessed": bytes_accessed,
            "time_est": max(t_flops, t_mem)}


def _config_key(cfg: Dict) -> str:
    return json.dumps(cfg, sort_keys=True)


def _features(cfg: Dict, est: Dict, keys: List[str]) -> List[float]:
    """Feature vector over a FIXED key set (configs may carry different
    keys; absent ones read 0 so every vector has the same length)."""
    vals = [float(cfg.get(k, 0) or 0) for k in keys]
    return vals + [math.log1p(est.get("time_est", 0.0) * 1e6),
                   math.log1p(est.get("peak_bytes", 0) / 2 ** 20),
                   math.log1p(est.get("flops", 0.0) / 1e9)]


class _ResidualModel:
    """Least-squares throughput predictor over config features + the
    roofline estimate (the reference's XGBoostCostModel role)."""

    def __init__(self):
        self._w = None

    def fit(self, X: List[List[float]], y: List[float]):
        A = np.asarray(X, np.float64)
        A = np.concatenate([A, np.ones((A.shape[0], 1))], axis=1)
        b = np.asarray(y, np.float64)
        # ridge for stability on tiny trial counts
        lam = 1e-3 * np.eye(A.shape[1])
        self._w = np.linalg.solve(A.T @ A + lam, A.T @ b)

    def predict(self, X: List[List[float]]) -> np.ndarray:
        A = np.asarray(X, np.float64)
        A = np.concatenate([A, np.ones((A.shape[0], 1))], axis=1)
        return A @ self._w


class ModelBasedAutotuner:
    """Two-stage tuner over an explicit candidate list.

    ``build_fn(candidate) -> runner`` where the runner exposes
    ``estimate() -> {"peak_bytes", "flops", "time_est"}`` (cheap, AOT —
    see :func:`aot_estimate`) and ``step()`` (one training step,
    called warmup+measure times only for candidates the model selects).

    Stage 1 prunes every candidate whose ``peak_bytes`` exceeds
    ``hbm_budget_bytes`` — predicted OOM, never timed. Stage 2 measures
    ``init_num`` roofline-best candidates, then alternates fit → pick
    best predicted unmeasured (with the reference's 0.2 random
    exploration) → measure, until ``max_trials`` (default: half the
    space, the verdict's budget) or ``early_stop`` trials without
    improvement. State persists to ``state_path`` after every
    measurement and resumes seamlessly."""

    def __init__(self, build_fn: Callable[[Dict], object],
                 space: List[Dict], *,
                 hbm_budget_bytes: Optional[int] = None,
                 init_num: int = INIT_NUM,
                 max_trials: Optional[int] = None,
                 early_stop: int = 4,
                 warmup_steps: int = 1, measure_steps: int = 3,
                 state_path: Optional[str] = None,
                 rng_seed: int = 0):
        if not space:
            raise ValueError("empty tuning space")
        self.build_fn = build_fn
        self.space = list(space)
        self.hbm_budget_bytes = hbm_budget_bytes
        self.init_num = max(1, init_num)
        self.max_trials = max_trials or max(1, len(space) // 2)
        self.early_stop = early_stop
        self.warmup_steps = warmup_steps
        self.measure_steps = measure_steps
        self.state_path = state_path
        self._rng = np.random.default_rng(rng_seed)
        self.results: List[ExperimentResult] = []
        self.pruned: List[Dict] = []
        self.estimates: Dict[str, Dict] = {}
        self.measured: Dict[str, float] = {}
        self.failed: Dict[str, str] = {}
        self._feat_keys = sorted(
            {k for c in space for k, v in c.items()
             if isinstance(v, (int, float, bool))})
        self._load_state()

    # ---------------- persistence (reference scheduler.py) ----------- #
    def _load_state(self):
        if not (self.state_path and os.path.exists(self.state_path)):
            return
        try:
            with open(self.state_path) as fh:
                st = json.load(fh)
            self.measured = {k: float(v)
                             for k, v in st.get("measured", {}).items()}
            self.failed = dict(st.get("failed", {}))
            self.estimates = st.get("estimates", {})
            logger.info(f"autotune: resumed {len(self.measured)} measured "
                        f"trials from {self.state_path}")
        except (OSError, json.JSONDecodeError) as e:
            logger.warning(f"autotune: could not resume state: {e}")

    def _save_state(self):
        if not self.state_path:
            return
        tmp = self.state_path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump({"measured": self.measured, "failed": self.failed,
                       "estimates": self.estimates}, fh)
        os.replace(tmp, self.state_path)

    # ---------------- measurement ------------------------------------ #
    def _measure(self, cfg: Dict) -> ExperimentResult:
        key = _config_key(cfg)
        if key in self.failed:
            # a failure stays a failure across resume — never replayed
            # as a 0-throughput "success"
            return ExperimentResult(cfg, error=self.failed[key])
        if key in self.measured:
            return ExperimentResult(cfg, throughput=self.measured[key])
        runner = None
        try:
            runner = self.build_fn(cfg)
            for _ in range(self.warmup_steps):
                runner.step()
            t0 = time.perf_counter()
            for _ in range(self.measure_steps):
                runner.step()
            dt = (time.perf_counter() - t0) / self.measure_steps
            tput = float(cfg.get("micro_batch", 1)) / dt
            self.measured[key] = tput
            self._save_state()
            return ExperimentResult(cfg, throughput=tput)
        except Exception as e:   # OOM / trace failure = failed experiment
            self.failed[key] = type(e).__name__
            self._save_state()
            return ExperimentResult(cfg, error=type(e).__name__)
        finally:
            # a failed runner's buffers must not haunt the next trial
            close = getattr(runner, "close", None)
            if close:
                try:
                    close()
                except Exception:
                    pass

    # ---------------- tuning loop ------------------------------------ #
    def tune(self) -> ExperimentResult:
        # stage 1: estimate everything, prune predicted OOM
        viable: List[Dict] = []
        for cfg in self.space:
            key = _config_key(cfg)
            if key not in self.estimates:
                runner = None
                try:
                    runner = self.build_fn(cfg)
                    self.estimates[key] = dict(runner.estimate())
                except Exception as e:
                    self.estimates[key] = {"error": type(e).__name__}
                finally:
                    close = getattr(runner, "close", None)
                    if close:
                        try:
                            close()
                        except Exception:
                            pass
            est = self.estimates[key]
            if "error" in est:
                self.pruned.append(cfg)
                logger.info(f"autotune: pruned (estimate failed "
                            f"{est['error']}): {cfg}")
            elif (self.hbm_budget_bytes
                    and est.get("peak_bytes", 0) > self.hbm_budget_bytes):
                self.pruned.append(cfg)
                logger.info(
                    f"autotune: pruned (predicted "
                    f"{est['peak_bytes'] / 2**30:.2f} GiB > budget): {cfg}")
            else:
                viable.append(cfg)
        self._save_state()
        if not viable:
            raise RuntimeError(
                f"all {len(self.space)} candidates pruned by the memory "
                "estimate; raise hbm_budget_bytes or shrink the configs")

        # stage 2: roofline-seeded model-guided measurement
        by_roofline = sorted(
            viable,
            key=lambda c: self.estimates[_config_key(c)].get(
                "time_est", 0.0))
        to_measure = by_roofline[:self.init_num]
        measured_cfgs: List[Dict] = []
        best: Optional[ExperimentResult] = None
        stale = 0
        trials = 0
        model = _ResidualModel()

        def remaining():
            done = {_config_key(c) for c in measured_cfgs}
            return [c for c in viable if _config_key(c) not in done]

        while trials < self.max_trials:
            if not to_measure:
                rest = remaining()
                if not rest:
                    break
                ok_cfgs = [c for c in measured_cfgs
                           if _config_key(c) in self.measured]
                X = [_features(c, self.estimates[_config_key(c)],
                               self._feat_keys) for c in ok_cfgs]
                y = [self.measured[_config_key(c)] for c in ok_cfgs]
                if len(X) >= 2:
                    model.fit(X, y)
                    Xr = [_features(c, self.estimates[_config_key(c)],
                                    self._feat_keys) for c in rest]
                    pred = model.predict(Xr)
                    pick = rest[int(np.argmax(pred))]
                else:
                    pick = rest[0]
                if self._rng.random() < RANDOM_EXPLORATION and \
                        len(rest) > 1:
                    pick = rest[int(self._rng.integers(len(rest)))]
                to_measure = [pick]
            cfg = to_measure.pop(0)
            res = self._measure(cfg)
            self.results.append(res)
            measured_cfgs.append(cfg)
            trials += 1
            logger.info(f"autotune trial {trials}/{self.max_trials}: {res}")
            if res.ok and (best is None or res.throughput >
                           best.throughput):
                best = res
                stale = 0
            else:
                stale += 1
                if stale >= self.early_stop:
                    logger.info("autotune: early stop "
                                f"({stale} trials without improvement)")
                    break
        if best is None:
            raise RuntimeError("no measured candidate succeeded")
        logger.info(f"autotune best: {best}")
        return best

    # ---------------- artifact (reference ds_config_optimal.json) ---- #
    def write_results(self, out_dir: str) -> str:
        """Reference-style artifact directory: ``ds_config_optimal.json``
        (the winning candidate), plus the full ledger."""
        os.makedirs(out_dir, exist_ok=True)
        ok = [r for r in self.results if r.ok]
        if not ok:
            raise RuntimeError("nothing to write: no successful trials")
        best = max(ok, key=lambda r: r.throughput)
        with open(os.path.join(out_dir, "ds_config_optimal.json"),
                  "w") as fh:
            json.dump(best.config, fh, indent=2)
        ledger = {
            "measured": [
                {"config": r.config, "throughput": r.throughput,
                 "error": r.error} for r in self.results],
            "pruned": self.pruned,
            "space_size": len(self.space),
            "trials": len(self.results),
        }
        with open(os.path.join(out_dir, "autotuning_results.json"),
                  "w") as fh:
            json.dump(ledger, fh, indent=2)
        return out_dir
