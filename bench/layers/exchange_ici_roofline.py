"""Per cent of the chip's interconnect peak that the collectives reach: the
bytes of REAL candidate rows that leave one chip in a search
(bench/shapes_mesh.py `routed_bytes_leaving_a_chip`: the pins' generated
states, (D - 1) / D of a chip's share of them, at the row's width; the
padding of the capacity-sized buckets is NOT counted, so a wider bucket
cannot raise the share) over the peak (bench/peaks.json `ici_bits_per_s`,
by device_kind), against the device self seconds under
`jaxmc.mesh.exchange` ALONE: the `all_to_all`s or `all_gather`s and the
unpacking of what arrived.  The route's sort, gathers and bucket scatters
are compute, not link traffic; `exchange_device_s` has them.

The 1600 Gbit/s in peaks.json is a v5e chip's aggregate over ALL its links;
a 2x2 host wires fewer of them, and the buckets cross the links full
whatever they hold, so the share reads low and cannot pass 100 %.  None
where no operation of the searches carries the scope."""

import os

import spans
from lib import load_json, load_module

SCOPE = "jaxmc.mesh.exchange"


def read(run):
    an = spans.of_run(run)
    if an is None or not an["scope_s"].get(SCOPE):
        return None
    bench = run["bench_dir"]
    mesh = load_module(os.path.join(bench, "shapes_mesh.py"),
                       "bench_shapes_mesh")
    nbytes = mesh.routed_of_run(run)
    if nbytes is None:
        return None
    shapes = load_module(os.path.join(bench, "shapes.py"), "bench_shapes")
    peak = shapes.peak_for(run["out"]["device"]["kind"],
                           load_json(os.path.join(bench, "peaks.json")))
    return mesh.ici_share(nbytes, an["scope_s"][SCOPE] / an["searches"],
                          peak["ici_bits_per_s"])
