"""The kinds of loop. A loop module has a ``Cell`` with the stages
``inputs``, ``setup``, ``window``, ``check`` and ``close`` that
``run.py`` drives, ``end_to_end()`` and ``traced_rates()`` for what it
measured, ``facts`` for the readers, and ``attempted`` / ``failed``."""


class CellBase:
    def __init__(self, *, config, traffic, seed, seconds, traced, stamps,
                 devs, say, cache_dir):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.seconds, self.traced, self.stamps = seconds, traced, stamps
        self.devs, self.say, self.cache_dir = devs, say, cache_dir
        self.facts = {}
        self.attempted = self.failed = 0

    def close(self) -> None:
        pass
