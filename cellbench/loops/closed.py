"""The ``closed`` loop: a fixed pool of clients, each sending its next
query when the last is answered."""

from .serve import Cell  # noqa: F401
