"""Load imbalance of the held experts over the window's decode steps: Σ of
the largest held expert's pairs (per layer, summed over layers) ÷ Σ of the
mean held expert's pairs, from THAPI's ``moe_route`` records.  1.0 is even;
the largest expert is the straggler of a grouped matmul."""


def read(ctx):
    r = (ctx.get("route") or {}).get("decode")
    if not r or not r["mean_load"]:
        return None
    return r["max_load"] / r["mean_load"]
