"""MT4G-equivalent CLI: discover and report a device topology.

    PYTHONPATH=src python examples/discover_topology.py --device sim-h100 -j out.json
    PYTHONPATH=src python examples/discover_topology.py --device host --quick
    PYTHONPATH=src python examples/discover_topology.py --device pallas -p
    PYTHONPATH=src python examples/discover_topology.py --device tpu -p   # on a TPU
    PYTHONPATH=src python examples/discover_topology.py --device sim-h100 \
        --store /tmp/topo-store        # second run: pure store hit, 0 probes

Mirrors the paper's tool surface: full-suite by default, JSON to stdout,
optional markdown report, per-family timing like §V-A.  ``--store DIR``
makes discovery read-/write-through the persistent topology store
(``--refresh`` forces a re-measure that still writes through).  ``tpu``
measures the attached chip and fails anywhere else; ``pallas`` runs the
same kernels in the interpreter against a modeled hierarchy.
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.core import SIM_DEVICES, discover_host, discover_pallas, discover_sim


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--device", default="sim-h100",
                    choices=sorted(SIM_DEVICES) + ["host", "pallas", "tpu"])
    ap.add_argument("--samples", type=int, default=17)
    ap.add_argument("--elements", nargs="*", default=None,
                    help="restrict to these memory elements (like mt4g CLI)")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--store", default=None, metavar="DIR",
                    help="persistent topology store directory "
                         "(read-through/write-through)")
    ap.add_argument("--refresh", action="store_true",
                    help="with --store: re-measure even on a stored hit")
    ap.add_argument("--adaptive", action="store_true",
                    help="adaptive coarse-to-fine sweeps (SweepBudget "
                         "defaults) instead of dense sweeps; identical "
                         "discrete attributes, a fraction of the probes "
                         "(the Pallas backend plans by default)")
    ap.add_argument("--gc-max-entries", type=int, default=None,
                    help="with --store: retention sweep after persisting "
                         "(keep at most N newest topologies)")
    ap.add_argument("-j", "--json-out", default=None)
    ap.add_argument("-p", "--markdown", action="store_true")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    store = None
    if args.store:
        from repro.core.engine.store import TopologyStore
        store = TopologyStore(args.store)
    gc_policy = None
    if args.gc_max_entries is not None:
        from repro.core import GcPolicy
        gc_policy = GcPolicy(max_entries=args.gc_max_entries)
    budget = None
    if args.adaptive:
        from repro.core import SweepBudget
        budget = SweepBudget()

    if args.device == "host":
        topo, timings = discover_host(quick=args.quick, store=store,
                                      refresh=args.refresh,
                                      gc_policy=gc_policy)
    elif args.device == "pallas":
        topo, timings = discover_pallas(n_samples=min(args.samples, 9),
                                        elements=args.elements,
                                        interpret=True, store=store,
                                        refresh=args.refresh,
                                        gc_policy=gc_policy)
    elif args.device == "tpu":
        topo, timings = discover_pallas(n_samples=min(args.samples, 9),
                                        store=store, refresh=args.refresh,
                                        gc_policy=gc_policy)
    else:
        dev = SIM_DEVICES[args.device](seed=0)
        topo, timings = discover_sim(dev, n_samples=args.samples,
                                     elements=args.elements, store=store,
                                     refresh=args.refresh, budget=budget,
                                     gc_policy=gc_policy)
    if store is not None:
        print(f"# store: {store.stats()}", file=sys.stderr)

    if args.markdown:
        print(topo.to_markdown())
    else:
        print(topo.dumps())
    print(f"\n# timings: total {timings.total:.2f}s "
          f"{ {k: round(v, 3) for k, v in timings.per_family.items()} }",
          file=sys.stderr)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(topo.dumps())
        print(f"# wrote {args.json_out}", file=sys.stderr)


if __name__ == "__main__":
    main()
