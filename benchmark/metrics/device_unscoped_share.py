"""Step program: the share of device time in operations with no module scope,
no step-level scope and no kernel name. Prints device ms per step by pass x
module (rows under 0.5% of the total are summed into ``other``), the same
with the layers of the stack summed (``graph_convs_*``), and by pass."""

import re

from lib import spans


def read(ctx):
    d = spans.device_by_scope(ctx)
    if not d or not d["total"]:
        return None
    rows = sorted(d["table"].items(), key=lambda kv: -kv[1])
    small = sum(t for _, t in rows if t < 0.005 * d["total"])
    ctx["say"](f"device ms per step by pass x module, of {d['total']:.3f}: " + ", ".join(
        f"{tag} {mod} {t:.3f}" for (tag, mod), t in rows if t >= 0.005 * d["total"])
        + f", other {small:.3f}")
    by_pass, layers_summed = {}, {}
    for (tag, mod), t in rows:
        by_pass[tag] = by_pass.get(tag, 0.0) + t
        key = (tag, re.sub(r"^graph_convs_\d+", "graph_convs_*", mod))
        layers_summed[key] = layers_summed.get(key, 0.0) + t
    ctx["say"]("device ms per step by pass x module, layers summed: " + ", ".join(
        f"{tag} {mod} {t:.3f}" for (tag, mod), t in sorted(
            layers_summed.items(), key=lambda kv: -kv[1]) if t >= 0.0005 * d["total"]))
    ctx["say"]("device ms per step by pass: " + ", ".join(
        f"{tag} {t:.3f}" for tag, t in sorted(by_pass.items(), key=lambda kv: -kv[1])))
    return 100.0 * d["unscoped"] / d["total"]
