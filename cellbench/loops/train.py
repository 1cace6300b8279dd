"""The ``train`` loop: whole trainings, one after another, on ratings
packed once."""

from __future__ import annotations

import time

import numpy as np

from .. import data, reference, trace
from . import CellBase


class Cell(CellBase):
    def inputs(self) -> None:
        base = data.base_ratings(self.config["dataset"], self.cache_dir)
        self.n_users, self.n_items = base[3], base[4]
        self.users, self.items, self.stars = data.relabel(*base, self.seed)

    def setup(self) -> None:
        import jax
        from predictionio_tpu.models.als import (
            ALSParams, RatingsCOO, pack_ratings, train_als)

        self.params = ALSParams(**self.config["params"],
                                seed=self.seed % (2 ** 31 - 1))
        coo = RatingsCOO(self.users, self.items, self.stars,
                         self.n_users, self.n_items)
        with self.stamps.stage("pack"):
            packed = pack_ratings(coo, self.params)
            jax.block_until_ready(jax.tree_util.tree_leaves(
                (packed.user_h, packed.item_h)))

        def train():
            return jax.block_until_ready(
                train_als(None, self.params, packed=packed))

        self.train = train  # the one call set-up warms and the window times
        with self.stamps.stage("warm"):
            self.out = train()
        self.facts["shapes"] = {"als_iteration": {
            "n_users": self.n_users, "n_items": self.n_items,
            "n_ratings": len(self.users), "rank": self.params.rank}}
        self.facts["units"] = {"iteration": self.params.num_iterations}

    def window(self) -> None:
        calls, t0 = [], time.perf_counter()
        while time.perf_counter() - t0 < self.seconds or (
                self.traced and len(calls) < 3):
            t = time.perf_counter()
            if self.traced and len(calls) in (1, 2):
                with trace.Capture(python=len(calls) == 2) as cap:
                    self.out = self.train()
                dt = cap.window_s
                if self.devs[0].platform == "cpu":
                    pass  # a rehearsal: the CPU trace has no device plane
                elif len(calls) == 1:
                    self.facts["trace"] = trace.reduce_device(
                        cap.events, cap.window_s)
                    ops = trace.top_device_ops(self.facts["trace"])
                else:
                    self.facts["breakdown"] = {
                        "device_ops": ops,
                        "idle_gaps": trace.idle_gaps(cap.events)}
            else:
                self.out = self.train()
                dt = time.perf_counter() - t
            calls.append(dt)
        self.elapsed = time.perf_counter() - t0
        self.calls = calls
        self.attempted = len(calls)

    def _rate(self, seconds: float, n_calls: int = 1) -> float:
        return (len(self.users) * self.params.num_iterations * n_calls
                / seconds)

    def end_to_end(self) -> dict:
        if self.traced:  # stop_trace sits between calls: rate the calls
            return {"train_ratings_per_s": self._rate(self.calls[0])}
        return {"train_ratings_per_s":
                self._rate(self.elapsed, len(self.calls))}

    def traced_rates(self) -> dict:
        return {"untraced_call": self._rate(self.calls[0]),
                "traced_call_python_off": self._rate(self.calls[1]),
                "traced_call_python_on": self._rate(self.calls[2])}

    def check(self) -> list:
        """The last training of the window against the plain reference.

        Items were solved last, so every item row has to satisfy its own
        normal equations against the final user table: the number
        compared is the mean relative residual over a seeded sample of
        rows, which the lower-precision gather (the control) doubles.
        User rows were solved one half-iteration earlier, against an
        item table that has since moved: their distance from the exact
        solve is a convergence gap, steady from seed to seed, and is
        held against a training that returns its state unchanged or
        loses part of the ratings."""
        lim = self.config["check"]
        U = np.asarray(self.out[0])[:self.n_users]
        V = np.asarray(self.out[1])[:self.n_items]
        rng = np.random.default_rng([self.seed, 0xc4ec])
        kw = dict(reg=self.params.reg, alpha=self.params.alpha,
                  scale_reg=self.params.scale_reg_by_count)

        def sample(size):
            return np.sort(rng.choice(
                size, min(int(lim["rows_sampled"]), size), replace=False))

        def finite(values):
            return float(values.mean()) if np.isfinite(values).all() \
                else float("inf")

        hist = reference.histories(self.items, self.users, self.stars,
                                   sample(self.n_items))
        res = reference.residuals(V, U, hist, **kw)
        self.say("check_detail", {"side": "item", "rows": len(res),
                                  "residual_max": float(res.max())})
        hist = reference.histories(self.users, self.items, self.stars,
                                   sample(self.n_users))
        ugaps = reference.row_gaps(U, reference.als_rows(V, hist, **kw))
        self.say("check_detail", {"side": "user", "rows": len(ugaps),
                                  "gap_max": float(ugaps.max())})
        return [{"name": "item_row_residual_mean", "value": finite(res),
                 "limit": lim["item_row_residual_mean"]},
                {"name": "user_row_gap_mean", "value": finite(ugaps),
                 "limit": lim["user_row_gap_mean"]}]
