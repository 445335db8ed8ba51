"""Faults planted in the timed path, to show that a cell's `correct` catches
them.  Each driver lists the faults its path can have in `FAULTS` and
plants one with `plant(fault)`, a context that patches the program's
objects for one run; this file only finds them by the driver's name.

One run with a fault planted, on the chip (the benchmark's own runs never
plant one):

    python3 bench/faults.py --fault half_batch --workload <cell> \
        --seed <n> --seconds <s>
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def planted(driver: str, fault: str, reg=None):
    """A context in which `fault` is planted in `driver`'s timed path."""
    from bench import registry
    mod = (reg or registry.Registry()).driver(driver)
    if fault not in mod.FAULTS:
        raise KeyError(f"driver {driver!r} has no fault {fault!r} "
                       f"(known: {mod.FAULTS})")
    return mod.plant(fault)


def main(argv=None) -> int:
    import argparse
    from bench import harness, registry
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True)
    ap.add_argument("--workload", required=True)
    a, rest = ap.parse_known_args(argv)
    reg = registry.Registry()
    driver = reg.traffic(reg.cell(a.workload)["traffic"])["driver"]
    with planted(driver, a.fault, reg):
        return harness.main(["--workload", a.workload] + rest)


if __name__ == "__main__":
    sys.exit(main())
