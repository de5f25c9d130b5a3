"""Programs that went through jax's back end up to the end of the traced
search (``search_report["process"]["n_programs"]``; the window builds none,
so these are set-up's).  Also prints the ``setup:`` line: set-up's parts in
order on the program's clock, their sum beside the traced search's start,
and the builds by start.  ``None`` on a program without the block."""

import json


def parts_of(process):
    """Set-up in order, up to the start of the process's second ``fit``
    (the traced search): name -> seconds."""
    fits = process.get("fits") or []
    if len(fits) < 2 or process.get("first_call_s") is None:
        return None
    warm, traced = fits[0], fits[1]
    return {
        "import": process["import_s"],
        "before_first_call": process["first_call_s"] - process["import_s"],
        "first_call_to_first_fit": warm["t0_s"] - process["first_call_s"],
        "first_fit": warm["t1_s"] - warm["t0_s"],
        "first_fit_to_traced_search": traced["t0_s"] - warm["t1_s"],
    }


def read(ctx):
    process = ctx["report"].get("process")
    if not process:
        return None
    parts = parts_of(process)
    if parts is not None:
        print("setup: " + json.dumps({
            "parts_s": {k: round(v, 4) for k, v in parts.items()},
            "sum_s": round(sum(parts.values()), 4),
            "traced_search_t0_s": round(process["fits"][1]["t0_s"], 4),
            "import_by_root_s": {
                k: round(v, 4)
                for k, v in process["import_by_root"].items()},
            "import_own_s": round(process["import_own_s"], 4),
            "totals": {k: round(process[k], 4) for k in (
                "trace_s", "lower_s", "xla_s", "cache_load_s",
                "build_union_s", "build_blocked_s")},
            "cache": [process["n_cache_hits"], process["n_cache_misses"]],
            # t0_s, name, thread, seconds, cache, blocking
            "builds": [[round(b["t0_s"], 3), b["label"] or b["name"],
                        b["thread"], round(b["t1_s"] - b["t0_s"], 3),
                        b["cache"], int(b["blocking"])]
                       for b in process["builds"]],
        }), flush=True)
    return process["n_programs"]
