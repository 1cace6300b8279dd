"""The ``open`` loop: arrivals at a rate fixed in the traffic file,
each request timed from the instant it was due."""

from .serve import Cell  # noqa: F401
