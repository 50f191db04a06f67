"""Multi-process async elastic ring launcher.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.ring_async_run \
        --family link_like --scale 0.02 --m 400 --k 2 --max-rounds 4

The parent samples the benchmark BN, partitions the edges, allocates one
TCP port per ring member, and spawns **one OS process per member** — each
runs :func:`repro.core.ring_async.run_member` (the same unit the threaded
mode and ``cges(engine="async")`` execute) over the localhost data plane,
then writes its result to the shared workdir for the parent to aggregate.

``--jax-distributed`` additionally forms a ``jax.distributed`` cluster
before the members start (coordinator on the parent-chosen port, env
triplet from ``launch.devices.jax_distributed_env``).  On the CPU backend
this is cluster **bootstrap only** — cross-process collectives aren't
implemented there, and the coordination service hard-terminates surviving
processes when a peer dies.  For exactly that reason the kill-one-member
drill (``--die-member I --die-after-round R``: member I hard-exits with
``os._exit(13)`` after posting round R's BN) refuses to combine with
``--jax-distributed``; the survivors re-partition the dead member's edge
subset and finish with k-1 members on our own sockets.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

DIE_EXIT_CODE = 13


# ---------------------------------------------------------------------------
# Worker: one ring member in this process
# ---------------------------------------------------------------------------

def worker_main(spec_path: str) -> int:
    with open(spec_path) as f:
        w = json.load(f)
    # coordinator triplet travels via env (launch.devices.jax_distributed_env)
    # and must be consumed before ANY jax computation — importing repro.core
    # already warms the backend, so the cluster bootstrap happens right here
    # rather than inside run_member
    coord = os.environ.get("REPRO_JAX_COORDINATOR") or None
    if coord is not None:
        import jax

        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(os.environ["REPRO_JAX_NUM_PROCS"]),
            process_id=int(os.environ["REPRO_JAX_PROC_ID"]))

    from ..core.ges import GESConfig
    from ..core.ring_async import AsyncRingSpec, run_member

    z = np.load(w["problem"], allow_pickle=False)
    config = GESConfig(**w["config"])
    spec = AsyncRingSpec(
        member_id=int(w["member_id"]),
        peers=tuple((int(i), str(h), int(p)) for i, h, p in w["peers"]),
        max_rounds=int(w["max_rounds"]),
        speculation=int(w["speculation"]),
        hb_timeout_s=float(w["hb_timeout_s"]),
        wall_limit_s=float(w["wall_limit_s"]),
        jax_coordinator=None,            # cluster already formed above
        die_after_round=(int(w["die_after_round"])
                         if w.get("die_after_round") is not None else None),
        die_hard=True,
    )
    res = run_member(z["data"], z["arities"], z["edge_masks"], spec,
                     config=config, add_limit=w.get("add_limit"))
    np.save(w["out"] + ".adj.npy", np.asarray(res["adj"], dtype=np.int8))
    scalars = {key: val for key, val in res.items()
               if key not in ("adj", "timings")}
    scalars["timings"] = {ph: float(np.sum(v))
                          for ph, v in res["timings"].items()}
    with open(w["out"] + ".json", "w") as f:
        json.dump(scalars, f)
    return 0


# ---------------------------------------------------------------------------
# Parent: spawn k members, aggregate
# ---------------------------------------------------------------------------

def _free_ports(count: int):
    """Reserve `count` distinct free ports (bind, record, close).  The
    children re-bind them; SO_REUSEADDR makes the tiny window benign on a
    CI loopback."""
    socks, ports = [], []
    for _ in range(count):
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _check_cpu_members() -> None:
    """Members are separate OS processes and an accelerator belongs to one
    process at a time, so they run on the CPU backend only — and the parent
    must not hold an accelerator either."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "ring_async_run starts one OS process per ring member, and an "
            "accelerator belongs to one process at a time, so its members "
            "run on the CPU backend only: set JAX_PLATFORMS=cpu.  On a TPU "
            "host run the members as threads of one process with "
            "cges(engine='async').")
    jax = sys.modules.get("jax")
    if jax is not None and jax.config.jax_platforms != "cpu":
        raise RuntimeError(
            f"the launching process imported JAX for platforms "
            f"{jax.config.jax_platforms!r} before spawning the members; it "
            f"may hold the accelerator — launch from a process with "
            f"JAX_PLATFORMS=cpu set before JAX is imported")


def launch_ring(data, arities, edge_masks, *, config_kwargs, add_limit=None,
                max_rounds=16, speculation=2, hb_timeout_s=3.0,
                wall_limit_s=300.0, jax_distributed=False, die_member=None,
                die_after_round=None, workdir=None, verbose=True) -> dict:
    """Spawn one OS process per ring member and aggregate their results.

    Returns the same aggregate shape as
    ``core.ring_async.run_ring_async_threads`` (graphs/scores/rounds/
    survivors/members/...), plus per-member exit codes.  Refuses to start
    unless the members (and this process) are on the CPU backend."""
    from .devices import jax_distributed_env

    _check_cpu_members()
    if jax_distributed and die_member is not None:
        raise ValueError(
            "--jax-distributed cannot be combined with a kill drill: the "
            "jax coordination service terminates surviving processes when "
            "a peer dies (see core/ring_async.py docstring)")
    k = int(np.asarray(edge_masks).shape[0])
    workdir = workdir or tempfile.mkdtemp(prefix="ring_async_")
    problem = os.path.join(workdir, "problem.npz")
    np.savez(problem, data=data, arities=arities, edge_masks=edge_masks)

    n_ports = k + (1 if jax_distributed else 0)
    ports = _free_ports(n_ports)
    peers = [[i, "127.0.0.1", ports[i]] for i in range(k)]
    coordinator = f"127.0.0.1:{ports[k]}" if jax_distributed else None

    procs = []
    for i in range(k):
        spec_path = os.path.join(workdir, f"member_{i}.spec.json")
        with open(spec_path, "w") as f:
            json.dump({
                "member_id": i,
                "peers": peers,
                "problem": problem,
                "out": os.path.join(workdir, f"member_{i}"),
                "config": config_kwargs,
                "add_limit": add_limit,
                "max_rounds": max_rounds,
                "speculation": speculation,
                "hb_timeout_s": hb_timeout_s,
                "wall_limit_s": wall_limit_s,
                "die_after_round": (die_after_round if i == die_member
                                    else None),
            }, f)
        env = dict(os.environ)
        if coordinator is not None:
            env.update(jax_distributed_env(coordinator, k, i))
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.launch.ring_async_run",
             "--worker", spec_path],
            env=env, cwd=os.getcwd()))

    deadline = time.monotonic() + wall_limit_s + 60.0
    rcs = {}
    for i, p in enumerate(procs):
        try:
            rcs[i] = p.wait(timeout=max(deadline - time.monotonic(), 1.0))
        except subprocess.TimeoutExpired:
            p.kill()
            rcs[i] = -9
    if verbose:
        print(f"[parent] exit codes: {rcs}")

    results = {}
    for i in range(k):
        out = os.path.join(workdir, f"member_{i}")
        if rcs[i] == 0 and os.path.exists(out + ".json"):
            with open(out + ".json") as f:
                results[i] = json.load(f)
            results[i]["adj"] = np.load(out + ".adj.npy")
    survivors = sorted(results)
    if not survivors:
        raise RuntimeError(
            f"async ring launch: no surviving members (exit codes {rcs})")
    rep = results[survivors[0]]
    agg = {
        "graphs": np.stack([results[i]["adj"] for i in survivors]),
        "scores": np.array([results[i]["score"] for i in survivors]),
        "rounds": int(max(results[i]["rounds"] for i in survivors)),
        "live": rep["live"],
        "members": results,
        "survivors": survivors,
        "exit_codes": rcs,
        "timed_out": any(results[i]["timed_out"] for i in survivors),
        "workdir": workdir,
    }
    agg["best_member"] = survivors[int(np.argmax(agg["scores"]))]
    agg["best_adj"] = results[agg["best_member"]]["adj"]
    agg["best_score"] = float(agg["scores"].max())
    return agg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--family", default="link_like",
                    choices=["link_like", "pigs_like", "munin_like"])
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--m", type=int, default=400)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--limit", action="store_true")
    ap.add_argument("--max-rounds", type=int, default=8)
    ap.add_argument("--speculation", type=int, default=2)
    ap.add_argument("--counts-impl", default="fused")
    ap.add_argument("--max-q", type=int, default=256)
    ap.add_argument("--hb-timeout", type=float, default=3.0)
    ap.add_argument("--wall-limit", type=float, default=300.0)
    ap.add_argument("--jax-distributed", action="store_true",
                    help="form a jax.distributed cluster before the members "
                         "start (bootstrap only on CPU; incompatible with "
                         "--die-member)")
    ap.add_argument("--die-member", type=int, default=None)
    ap.add_argument("--die-after-round", type=int, default=None)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.worker is not None:
        raise SystemExit(worker_main(args.worker))

    from ..core.cges import edge_add_limit
    from ..core import partition
    from ..data.bn import benchmark_bn, forward_sample

    t0 = time.time()
    bn = benchmark_bn(args.family, scale=args.scale, seed=args.seed)
    data = forward_sample(bn, args.m, np.random.default_rng(args.seed + 1))
    n = bn.n
    masks = partition.partition_edges(data, bn.arities, args.k)
    lim = edge_add_limit(n, args.k) if args.limit else None
    print(f"{args.family} scale={args.scale}: n={n}, m={args.m}, "
          f"k={args.k} processes")

    agg = launch_ring(
        data, bn.arities, masks,
        config_kwargs={"max_q": args.max_q,
                       "counts_impl": args.counts_impl},
        add_limit=lim, max_rounds=args.max_rounds,
        speculation=args.speculation, hb_timeout_s=args.hb_timeout,
        wall_limit_s=args.wall_limit, jax_distributed=args.jax_distributed,
        die_member=args.die_member, die_after_round=args.die_after_round,
        workdir=args.workdir)

    out = {
        "family": args.family, "n": n, "m": args.m, "k": args.k,
        "jax_distributed": bool(args.jax_distributed),
        "die_member": args.die_member,
        "survivors": agg["survivors"],
        "live": agg["live"],
        "rounds": agg["rounds"],
        "scores": [float(s) for s in agg["scores"]],
        "best_score": agg["best_score"],
        "timed_out": agg["timed_out"],
        "exit_codes": {str(i): rc for i, rc in agg["exit_codes"].items()},
        "deaths": {str(i): agg["members"][i]["deaths"]
                   for i in agg["survivors"]},
        "timings_us": {str(i): agg["members"][i]["timings"]
                       for i in agg["survivors"]},
        "wall_s": round(time.time() - t0, 2),
    }
    print(json.dumps(out, indent=2))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
