"""Streaming incremental training (ISSUE 10): close the event→model loop.

The subsystem that takes model freshness from retrain cadence
(~minutes) to seconds: a :class:`StreamTrainer` daemon tails the event
log behind a durable :class:`EventCursor` (persisted through EVENTDATA,
bus-woken, catch-up-correct), folds micro-batches of fresh events into
the deployed ALS model via per-entity regularized least-squares solves
against the fixed opposite factors
(:func:`~predictionio_tpu.models.als.fold_in_rows` — the same
``_lhs_fn`` device path the batch trainer uses), canaries
every delta with a :class:`~predictionio_tpu.rollout.HealthPolicy`
probe, and hot-swaps updated rows into the live serving binding. A
:class:`DriftMonitor` demotes full retrains to a drift-triggered
background job. See docs/streaming.md.
"""

from .cursor import CURSOR_ENTITY_TYPE, EventCursor
from .drift import DriftMonitor
from .foldin import (
    DEFAULT_EVENT_WEIGHTS,
    FoldInReport,
    fold_in_events,
    project_ratings,
)
from .trainer import StreamConfig, StreamTrainer

__all__ = [
    "CURSOR_ENTITY_TYPE",
    "DEFAULT_EVENT_WEIGHTS",
    "DriftMonitor",
    "EventCursor",
    "FoldInReport",
    "StreamConfig",
    "StreamTrainer",
    "fold_in_events",
    "project_ratings",
]
