"""Launcher utilities: host parsing, slot allocation, free ports.

Capability parity with the reference launcher internals
(``horovod/run/run.py:384-398`` host parsing and
``horovod/run/gloo_run.py:51-109`` slot allocation); fresh implementation.
"""

import collections
import os
import socket

HostInfo = collections.namedtuple("HostInfo", ["hostname", "slots"])

SlotInfo = collections.namedtuple(
    "SlotInfo",
    ["hostname", "rank", "local_rank", "cross_rank", "size", "local_size",
     "cross_size"])


def parse_hosts(hosts_string):
    """Parses "host1:2,host2:2" into HostInfo list ("host" implies 1 slot)."""
    hosts = []
    for part in hosts_string.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            name, slots = part.rsplit(":", 1)
            hosts.append(HostInfo(name, int(slots)))
        else:
            hosts.append(HostInfo(part, 1))
    return hosts


def parse_hostfile(path):
    """Hostfile lines: "hostname slots=N" (or just "hostname")."""
    hosts = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            fields = line.split()
            slots = 1
            for field in fields[1:]:
                if field.startswith("slots="):
                    slots = int(field[len("slots="):])
            hosts.append(HostInfo(fields[0], slots))
    return hosts


def allocate_slots(hosts, np):
    """Assigns np ranks to host slots in order; computes local/cross ranks.

    Mirrors the reference allocation semantics (gloo_run.py:51-109): ranks
    fill hosts in order, local_rank counts within a host, cross_rank indexes
    the host among hosts that have a slot at that local_rank.
    """
    total_slots = sum(h.slots for h in hosts)
    if np > total_slots:
        raise ValueError(
            "requested %d processes but only %d slots available" %
            (np, total_slots))
    slots = []
    rank = 0
    host_idx_assigned = []  # (host_index, local_rank) per rank
    local_sizes = collections.defaultdict(int)
    for hi, host in enumerate(hosts):
        for local_rank in range(host.slots):
            if rank >= np:
                break
            host_idx_assigned.append((hi, local_rank, host.hostname))
            local_sizes[hi] += 1
            rank += 1
    # cross structures: for a given local_rank, ranks across hosts.
    cross_groups = collections.defaultdict(list)  # local_rank -> [host_index]
    for hi, local_rank, _ in host_idx_assigned:
        if hi not in cross_groups[local_rank]:
            cross_groups[local_rank].append(hi)
    for rank, (hi, local_rank, hostname) in enumerate(host_idx_assigned):
        cross_ranks = cross_groups[local_rank]
        slots.append(SlotInfo(
            hostname=hostname,
            rank=rank,
            local_rank=local_rank,
            cross_rank=cross_ranks.index(hi),
            size=np,
            local_size=local_sizes[hi],
            cross_size=len(cross_ranks),
        ))
    return slots


def topology_env(rank, host_ports):
    """Computes the HVD_TPU_* env for `rank` given every rank's "host:port"
    (index == rank). Topology semantics shared by the launcher, the Spark
    barrier tasks and rank-subset init: local = same host, cross = same
    local_rank across hosts."""
    size = len(host_ports)
    hosts = [hp.rsplit(":", 1)[0] for hp in host_ports]
    by_host = collections.defaultdict(list)
    for r, h in enumerate(hosts):
        by_host[h].append(r)
    my_host = hosts[rank]
    local_ranks = by_host[my_host]
    local_rank = local_ranks.index(rank)
    # cross: hosts that have a rank at this local_rank, ordered by first
    # appearance.
    host_order = list(dict.fromkeys(hosts))
    cross_hosts = [h for h in host_order if len(by_host[h]) > local_rank]
    return {
        "HVD_TPU_RANK": str(rank),
        "HVD_TPU_SIZE": str(size),
        "HVD_TPU_LOCAL_RANK": str(local_rank),
        "HVD_TPU_LOCAL_SIZE": str(len(local_ranks)),
        "HVD_TPU_CROSS_RANK": str(cross_hosts.index(my_host)),
        "HVD_TPU_CROSS_SIZE": str(len(cross_hosts)),
        "HVD_TPU_ADDRS": ",".join(host_ports),
    }


def is_local_host(hostname):
    return hostname in ("localhost", "127.0.0.1", socket.gethostname())


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache(env=None):
    """Points ``env`` (default ``os.environ``) at JAX's persistent
    compile cache and returns the directory. Call before the process
    imports jax. Where ``JAX_COMPILATION_CACHE_DIR`` is set it is kept
    as it is; otherwise every process of the checkout shares one fixed
    directory inside it (git-ignored) — the path is part of the cache
    key, so a directory that moves never hits."""
    env = os.environ if env is None else env
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_CHECKOUT,
                                                        ".jax_cache")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return env["JAX_COMPILATION_CACHE_DIR"]


def cpu_worker_env(base_env=None, extra_env=None, repo_root=None):
    """Env for spawning CPU-only worker subprocesses: CPU backend
    pinned, shared jit compile cache. The SINGLE source of truth for
    this scrub:

    * pin ``JAX_PLATFORMS=cpu`` — these are CPU workers by definition;
      a chip belongs to one process at a time, so N local workers that
      all reach for it fail or hang;
    * pop ``PYTHONUNBUFFERED`` — with it set, every text write is its
      own raw write, so ``print(line)`` becomes TWO pipe writes
      (payload, then newline) and N workers sharing the launcher's
      stdout pipe interleave mid-line, corrupting line-oriented test
      protocols (observed: two COUNTERS JSON lines merged into one).
      Buffered stdout flushes a whole line atomically; workers that
      need promptness use ``print(..., flush=True)``;
    * the persistent compile cache (:func:`use_compile_cache`) so
      identical worker jit programs compile once across the fleet.
    """
    env = dict(base_env if base_env is not None else os.environ)
    if repo_root:
        env["PYTHONPATH"] = repo_root + os.pathsep + \
            env.get("PYTHONPATH", "")
    env.pop("PYTHONUNBUFFERED", None)
    env["JAX_PLATFORMS"] = "cpu"
    use_compile_cache(env)
    if extra_env:
        env.update(extra_env)
    return env
