"""Layer: ingest_prepare. The sanity checker's statistics programs' share of
their roofline, in %: the least chip time of their work (the design's sample
read once, and the contingency contraction's operations:
``benchmark/costs_prepare.py`` over ``benchmark/peaks.json``) over the chip
seconds a traced train under the package's ``checkers.sanity_checker.SCOPES``
(``sanity.stats``, ``sanity.contingency``), whatever programs they sit in
(``benchmark/trace/scopes.py``). None without a traced window, without the
checker's shape, and where the trace shows none of those scopes (a package
without them, or an executable from a cache filled before them)."""
from benchmark import costs, costs_prepare, harness
from benchmark.layer_metrics.pool_forest_s import traced_trains
from benchmark.trace import scopes


def sanity_scopes():
    from transmogrifai_tpu.checkers import sanity_checker
    return tuple(getattr(sanity_checker, "SCOPES", ()))


def scoped_seconds(obs):
    """Chip seconds under the sanity scopes in the traced window, summed
    over programs and devices; None where there are none."""
    names = sanity_scopes()
    path = scopes.newest_trace() if (obs.get("trace") or {}).get(
        "devices") else None
    if not names or path is None:
        return None
    table = scopes.by_scope(scopes.load(path), names)
    seconds = sum(v for row in table.values()
                  for k, v in row["by_scope"].items() if k in names)
    return seconds or None


def read(obs):
    shape, trains = obs.get("sanity_shape"), traced_trains(obs)
    seconds = scoped_seconds(obs) if shape and trains else None
    if not seconds:
        return None
    least = costs.least_seconds(costs_prepare.sanity_stats_cost(**shape),
                                harness.load_peaks(obs["device_kind"]))
    harness.say(f"least chip time of the sanity statistics over "
                f"{shape['rows']} x {shape['columns']} "
                f"{least['seconds']:.6f} s, {least['bound']}-bound; the "
                f"sanity scopes took {seconds / trains:.6f} chip seconds a "
                f"train")
    return 100.0 * least["seconds"] * trains / seconds
