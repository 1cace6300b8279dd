"""TPU-native runtime gauges: XLA compiles, HBM occupancy, guard hits.

ALX-style TPU serving treats HBM occupancy and recompile counts as
first-class signals (PAPERS: Google ads-serving infrastructure) — a
recompile storm or HBM creep shows up in the tail long before it shows
up in an error log. These helpers register the process-level series on
any :class:`.registry.MetricsRegistry`; everything degrades gracefully
off-TPU (gauges read 0 or are simply absent).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from .guard import TransferGuardCounter
from .registry import MetricsRegistry


def hbm_stats() -> List[Dict[str, object]]:
    """Per-device HBM bytes in use / limit via ``device.memory_stats()``;
    empty off-TPU (CPU PJRT returns None), when jax is absent, or when
    no backend is initialized yet. NEVER initializes a backend itself:
    an event/storage server scraping /metrics must not acquire the TPU
    (operations.md "one chip, one tenant") just to report on it."""
    import sys

    if "jax" not in sys.modules:  # jax-free server: nothing to report,
        return []                 # and a scrape must not pay the import
    try:
        import jax
        from jax._src import xla_bridge

        if hasattr(xla_bridge, "backends_are_initialized") \
                and not xla_bridge.backends_are_initialized():
            return []
        devices = jax.devices()
    except Exception:  # noqa: BLE001 — observability never requires jax
        return []
    out: List[Dict[str, object]] = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — per-device degrade
            stats = None
        if not stats:
            continue
        out.append({
            "device": str(d.id),
            "kind": getattr(d, "device_kind", "unknown"),
            "bytesInUse": int(stats.get("bytes_in_use", 0)),
            "bytesLimit": int(stats.get("bytes_limit", 0) or
                              stats.get("bytes_reservable_limit", 0)),
            "peakBytesInUse": int(stats.get("peak_bytes_in_use", 0)),
        })
    return out


def build_info(server: str, version: Optional[str] = None
               ) -> Dict[str, object]:
    """The ``pio_build_info`` label set: package + jax versions, the
    live backend, process_count, and local/global device counts (the
    mesh denominators every bench line and trace is attributed
    against). Backend-dependent labels degrade to ``"none"`` rather
    than initializing a backend (the :func:`hbm_stats` discipline)."""
    import sys

    if version is None:
        try:
            from .. import __version__ as version
        except Exception:  # noqa: BLE001
            version = "unknown"
    info: Dict[str, object] = {"server": server, "version": version}
    if "jax" not in sys.modules:
        info.update(jax="none", backend="none", process_count=0,
                    devices=0)
        return info
    try:
        import jax

        info["jax"] = getattr(jax, "__version__", "unknown")
        from jax._src import xla_bridge

        if hasattr(xla_bridge, "backends_are_initialized") \
                and not xla_bridge.backends_are_initialized():
            info.update(backend="none", process_count=0, devices=0)
            return info
        info["backend"] = jax.default_backend()
        info["process_count"] = int(jax.process_count())
        info["devices"] = int(jax.device_count())
    except Exception:  # noqa: BLE001 — build info must never fail a
        info.setdefault("jax", "unknown")        # scrape
        info.setdefault("backend", "none")
        info.setdefault("process_count", 0)
        info.setdefault("devices", 0)
    return info


def _read(path: str) -> bytes:
    fd = os.open(path, os.O_RDONLY)
    try:
        return os.read(fd, 1024)
    finally:
        os.close(fd)


def _lock_held_reader() -> Callable[[str], bytes]:
    """``read(path) -> bytes`` through libc with the interpreter lock
    HELD. ``os.open`` / ``os.read`` / ``os.close`` each give the lock
    up, and a thread that gives it up in a server with 70 busy threads
    waits a millisecond or two to have it back (PERF.md finding 29.4):
    three times a task, a pass over 300 tasks would take a second and
    its readings would lie that far apart. A ``/proc`` read never
    blocks, so nothing is held up but for the microseconds it takes.
    The buffer is the returned function's own: one caller at a time.
    :func:`_read` where libc cannot be reached that way."""
    try:
        import ctypes

        libc = ctypes.PyDLL(None, use_errno=True)
        c_open, c_read, c_close = libc.open, libc.read, libc.close
        c_open.argtypes, c_open.restype = (
            ctypes.c_char_p, ctypes.c_int), ctypes.c_int
        c_read.argtypes, c_read.restype = (
            ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t), ctypes.c_ssize_t
        c_close.argtypes, c_close.restype = (ctypes.c_int,), ctypes.c_int
    except (ImportError, OSError, AttributeError):
        return _read
    buf = ctypes.create_string_buffer(1024)
    flags, size, string_at = os.O_RDONLY | os.O_CLOEXEC, len(buf), \
        ctypes.string_at

    def read(path: str) -> bytes:
        fd = c_open(path.encode(), flags)
        if fd < 0:
            raise OSError(ctypes.get_errno(), path)
        n = c_read(fd, buf, size)
        c_close(fd)
        if n < 0:
            raise OSError(ctypes.get_errno(), path)
        return string_at(buf, n)
    return read


def process_stats(read: Callable[[str], bytes] = _read
                  ) -> Dict[str, float]:
    """Host-resource self-read off ``/proc`` (Linux only, no psutil —
    the ISSUE 17 constraint): RSS bytes, cumulative CPU seconds
    (user+sys), open fd count, thread count. Empty dict where /proc is
    absent (macOS CI shards) — the gauges simply read 0 there."""
    out: Dict[str, float] = {}
    try:
        fields = read("/proc/self/statm").split()
        page = os.sysconf("SC_PAGESIZE")
        out["rss_bytes"] = float(int(fields[1]) * page)
    except Exception:  # noqa: BLE001 — absent /proc degrades to {}
        return {}
    try:
        # comm can contain spaces/parens: split after the LAST ")"
        rest = read("/proc/self/stat").rsplit(b")", 1)[1].split()
        tck = os.sysconf("SC_CLK_TCK")
        # rest[0] is field 3 (state); utime/stime are fields 14/15
        out["cpu_seconds_total"] = (int(rest[11]) + int(rest[12])) \
            / float(tck)
        out["threads"] = float(int(rest[17]))
    except Exception:  # noqa: BLE001
        pass
    try:
        out["open_fds"] = float(len(os.listdir("/proc/self/fd")))
    except Exception:  # noqa: BLE001
        pass
    return out


#: a Python thread's name prefix -> its role, first match wins. A Python
#: thread no prefix matches (the main thread among them) is ``other``; a
#: task of ``/proc/self/task`` that ``threading.enumerate()`` does not
#: know (the runtime's, the profiler's, the allocator's) is ``native``.
THREAD_ROLES: Tuple[Tuple[str, str], ...] = (
    ("http-handler", "handler"),
    ("http-acceptor", "acceptor"),
    ("pipeline-assemble", "assemble"),
    ("pipeline-dispatch", "dispatch"),
    ("pipeline-readback", "readback"),
    ("algo-dispatch", "supplement"),
    ("algo-batch-dispatch", "supplement"),
)
PYTHON_ROLES: Tuple[str, ...] = (
    "handler", "acceptor", "assemble", "dispatch", "readback",
    "supplement", "other")

_TASKS = "/proc/self/task"


def thread_role(name: str) -> str:
    """The role of the Python thread called ``name``."""
    for prefix, role in THREAD_ROLES:
        if name.startswith(prefix):
            return role
    return "other"


def name_os_thread(name: Optional[str] = None) -> None:
    """Called BY a thread as it starts (Python 3.12 names no OS thread):
    take ``name`` as the Python thread's name where one is given, and
    write the role's name to the task's ``comm`` (15 characters), so
    that ``top -H``, a profiler's host lines and :data:`THREAD_ROLES`
    agree. A thread without a role keeps the process's name."""
    me = threading.current_thread()
    if name is not None:
        me.name = name
    role = thread_role(me.name)
    if role == "other":
        return
    try:
        fd = os.open(f"{_TASKS}/{threading.get_native_id()}/comm",
                     os.O_WRONLY)
    except OSError:  # no /proc: the name is a convenience
        return
    try:
        os.write(fd, f"pio-{role}".encode())
    finally:
        os.close(fd)


class RoleThread(threading.Thread):
    """A ``threading.Thread`` that names its OS thread by its role
    (:func:`name_os_thread`) before it runs its target."""

    def run(self) -> None:
        name_os_thread()
        super().run()


class HostClocks:
    """The host's seconds by thread role, from the kernel's per-thread
    clocks: ``refresh()`` makes ONE pass over ``/proc/self/task`` and
    nothing runs in between (the kernel keeps the clocks whether
    anybody reads them). Every task's CPU and run-queue seconds since
    the pass before are credited to the role it has NOW, so a role's
    seconds only ever grow, also when its threads exit; ``exited`` is
    the process's CPU seconds less everything credited: what threads
    burned that were gone before a pass saw it. The ``cpu`` children
    therefore sum to the process's CPU seconds. Never imports jax."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._read = _lock_held_reader()
        #: tid -> (cpu s, run-queue s, role) at the pass before
        self._seen: Dict[int, Tuple[float, float, str]] = {}
        #: ``schedstat`` (nanoseconds on a core, nanoseconds runnable
        #: and waiting for one) where the kernel keeps it: a kernel
        #: without CONFIG_SCHED_INFO has no such file, one that keeps
        #: no scheduler statistics writes "0 0 0", a sandboxed one
        #: (gVisor) has neither. There a task's CPU clock, and no
        #: run-queue series.
        ran = self._task(threading.get_native_id(), schedstat=True)
        self.has_runqueue = ran is not None and ran[0] > 0.0
        self.seconds: Dict[Tuple[str, str], float] = {}
        self.counts: Dict[str, int] = {}
        self.cpus = 0
        self.process: Dict[str, float] = {}

    def _task(self, tid: int, schedstat: bool
              ) -> Optional[Tuple[float, float]]:
        try:
            if schedstat:
                ran, waited, _ = self._read(
                    f"{_TASKS}/{tid}/schedstat").split()
                return int(ran) * 1e-9, int(waited) * 1e-9
            # the thread's CPU clock, what pthread_getcpuclockid()
            # names: no file, and the interpreter lock is not given up
            return time.clock_gettime_ns((~tid << 3) | 6) * 1e-9, 0.0
        except (OSError, ValueError):
            return None  # the task exited under the pass

    def refresh(self) -> None:
        """One pass. Without ``/proc`` it leaves ``process`` empty,
        without ``/proc/self/task`` ``counts``."""
        with self._lock:
            self.process = process_stats(self._read)
            names = {t.native_id: t.name for t in threading.enumerate()}
            try:
                tids = os.listdir(_TASKS)
            except OSError:
                return
            seen: Dict[int, Tuple[float, float, str]] = {}
            counts: Dict[str, int] = {}
            for tid in map(int, tids):
                now = self._task(tid, self.has_runqueue)
                if now is None:
                    continue
                cpu, waited, role = self._seen.get(tid, (0.0, 0.0, "native"))
                if now[0] < cpu:  # the tid is another thread's now
                    cpu, waited, role = 0.0, 0.0, "native"
                name = names.get(tid)
                if name is not None:
                    role = thread_role(name)
                # else a thread Python has let go of and whose task is
                # not gone yet keeps, for this last pass, the role it had
                seen[tid] = (*now, "native" if name is None else role)
                counts[role] = counts.get(role, 0) + 1
                for state, grew in (("cpu", now[0] - cpu),
                                    ("runqueue", now[1] - waited)):
                    self.seconds[role, state] = self.seconds.get(
                        (role, state), 0.0) + max(grew, 0.0)
            self._seen, self.counts = seen, counts
            # read AFTER the tasks, so that it covers what they held;
            # CLOCK_PROCESS_CPUTIME_ID is the sum of the same clocks
            credited = sum(v for (role, state), v in self.seconds.items()
                           if state == "cpu" and role != "exited")
            total = self.process["cpu_seconds_total"] = time.process_time()
            self.seconds["exited", "cpu"] = max(
                self.seconds.get(("exited", "cpu"), 0.0), total - credited)
            self.cpus = len(os.sched_getaffinity(0))


def register_process_metrics(reg: MetricsRegistry) -> None:
    """Mount the host's half of a scale-out decision (a replica can be
    SLO-green and one fd leak or one core short of falling over), all
    read in ONE pass a render / export / snapshot
    (:meth:`HostClocks.refresh`, hooked before the registry collects)
    and shared by every child:

    - ``pio_process_{rss_bytes,cpu_seconds_total,open_fds,threads}``
    - ``pio_thread_seconds_total{role,state=cpu|runqueue}`` and
      ``{role="exited",state="cpu"}``, ``pio_thread_count{role}``:
      :class:`HostClocks`, a child a (role, state), made here
    - ``pio_host_cpus``: the cores this process may run on

    No-op where ``/proc`` is absent; without ``/proc/self/task`` the
    thread families are absent."""
    clocks = HostClocks()
    clocks.refresh()
    if not clocks.process:
        return
    reg.before_collect(clocks.refresh)

    # the names whole: the catalog lint (`ptpu check`) finds a family
    # by its literal
    for name, help in (
            ("pio_process_rss_bytes",
             "Resident set size of this server process "
             "(/proc/self/statm)"),
            ("pio_process_cpu_seconds_total",
             "Cumulative user+system CPU seconds of this process (the "
             "process's CPU clock, which /proc/self/stat's utime + "
             "stime add up to)"),
            ("pio_process_open_fds",
             "Open file descriptors (/proc/self/fd)"),
            ("pio_process_threads",
             "OS threads in this process (/proc/self/stat)")):
        reg.gauge(name, help, fn=lambda key=name[len("pio_process_"):]:
                  clocks.process.get(key, 0.0))
    if not clocks.counts:
        return
    seconds = reg.counter(
        "pio_thread_seconds_total",
        "CPU seconds (state=cpu) and seconds runnable but waiting for "
        "a core (state=runqueue, where the kernel keeps schedstat) of "
        "this process's threads by role; role=exited is what threads "
        "burned that no pass saw, so the cpu children sum to the "
        "process's CPU seconds")
    count = reg.gauge("pio_thread_count",
                      "Live OS threads of this process by role")
    states = ("cpu", "runqueue") if clocks.has_runqueue else ("cpu",)
    for role in PYTHON_ROLES + ("native",):
        for state in states:
            seconds.labels(role=role, state=state).set_fn(
                lambda key=(role, state): clocks.seconds.get(key, 0.0))
        count.labels(role=role).set_fn(
            lambda role=role: clocks.counts.get(role, 0))
    seconds.labels(role="exited", state="cpu").set_fn(
        lambda: clocks.seconds.get(("exited", "cpu"), 0.0))
    reg.gauge("pio_host_cpus",
              "Cores this process may run on (sched_getaffinity)",
              fn=lambda: clocks.cpus)


def register_runtime_metrics(reg: MetricsRegistry, server: str,
                             version: Optional[str] = None) -> None:
    """Mount the standard process-level series on ``reg``:

    - ``pio_build_info{server,version,jax,backend,process_count,
      devices}`` — constant-1 info gauge rendered at scrape time so
      bench lines and retained traces are attributable to the exact
      build/runtime that produced them; the jax/backend/device labels
      appear only once a backend is live (scraping NEVER initializes
      one) and refresh on the next scrape after deploy brings it up
    - ``pio_process_start_time_seconds``
    - ``pio_xla_compiles_total`` — lifetime XLA backend compiles
      (:class:`..server.stats.RecompileSentinel` listener)
    - ``pio_transfer_guard_violations_total`` — guard hits tallied by
      :class:`.guard.TransferGuardCounter`
    - ``pio_device_hbm_bytes{device,kind,stat=used|limit|peak}`` —
      per-device HBM occupancy, absent off-TPU
    - ``pio_process_{rss_bytes,cpu_seconds_total,open_fds,threads}``,
      ``pio_thread_seconds_total``, ``pio_thread_count``,
      ``pio_host_cpus`` — /proc self-read host-resource series
      (:func:`register_process_metrics`), absent without /proc
    """
    # idempotent per registry: a second build_app over the same
    # registry must not double-register the hbm/span collectors
    # (duplicate series would make the exposition invalid)
    if getattr(reg, "_runtime_mounted", False):
        return
    reg._runtime_mounted = True  # type: ignore[attr-defined]
    if version is None:
        try:
            from .. import __version__ as version
        except Exception:  # noqa: BLE001
            version = "unknown"
    from .registry import escape_label_value as _esc

    def _build_info_lines() -> List[str]:
        # render-time collector, not a statically-bound gauge: the
        # jax/backend/mesh labels describe whatever is live AT SCRAPE
        # TIME (a backend deploy brings up after mount still shows),
        # and a jax-free server never pays the import
        info = build_info(server, str(version))
        labels = ",".join(f'{k}="{_esc(str(v))}"'
                          for k, v in sorted(info.items()))
        return ["# HELP pio_build_info Constant 1; identifies the "
                "build and runtime being scraped",
                "# TYPE pio_build_info gauge",
                "pio_build_info{%s} 1" % labels]

    reg.register_collector(_build_info_lines)
    reg.gauge("pio_process_start_time_seconds",
              "Unix time this server process started"
              ).set(reg.start_time)

    def _compiles_total() -> float:
        # storage-only servers never import jax (the CLI skips it on
        # purpose); a /metrics scrape must not be the thing that pays
        # the import. When jax IS loaded, the sentinel's listener
        # installs once and the gauge reads the shared tally.
        import sys

        if "jax" not in sys.modules:
            return 0.0
        from ..server.stats import RecompileSentinel

        RecompileSentinel()  # idempotent listener install
        return float(RecompileSentinel.total_compiles())

    reg.gauge("pio_xla_compiles_total",
              "XLA backend compiles observed in this process",
              fn=_compiles_total)

    TransferGuardCounter.install()
    reg.gauge("pio_transfer_guard_violations_total",
              "Transfer-guard hits (implicit device<->host transfers "
              "observed under transfer_guard)",
              fn=TransferGuardCounter.total)

    # HBM is a render-time collector, not statically bound gauges:
    # devices that come up AFTER the server mounts its registry (deploy
    # initializes the backend when models land in HBM) still appear on
    # the next scrape, and a device-less server emits nothing.
    from .registry import escape_label_value, format_value

    def _hbm_lines() -> List[str]:
        stats = hbm_stats()
        if not stats:
            return []
        lines = ["# HELP pio_device_hbm_bytes Per-device HBM occupancy "
                 "from device.memory_stats(); absent off-TPU",
                 "# TYPE pio_device_hbm_bytes gauge"]
        for e in stats:
            for key, stat in (("bytesInUse", "used"),
                              ("bytesLimit", "limit"),
                              ("peakBytesInUse", "peak")):
                lines.append(
                    'pio_device_hbm_bytes{device="%s",kind="%s",stat="%s"} %s'
                    % (escape_label_value(str(e["device"])),
                       escape_label_value(str(e["kind"])), stat,
                       format_value(float(e[key]))))  # type: ignore[arg-type]
        return lines

    reg.register_collector(_hbm_lines)
    register_process_metrics(reg)
