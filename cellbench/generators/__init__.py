"""Seeded generators, found by the name a configuration's ``dataset``
or a traffic mix's ``arrivals`` gives: a new dataset or arrival process
arrives as a new module here.

A dataset module has ``generate(dataset) -> (users, items, stars,
n_users, n_items)`` and ``cache_name(dataset)``; an arrivals module has
``arrivals(traffic, seconds, seed) -> offsets in seconds``."""

import importlib


def find(name: str):
    return importlib.import_module(f"{__name__}.{name}")
