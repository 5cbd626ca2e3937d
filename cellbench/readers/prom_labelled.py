"""One label child of one of the program's Prometheus families, as it
stands SINCE THE PROCESS STARTED: the registry is read here, through
``utils.metrics.render()`` as ``cellbench/service.py::prom`` reads it
(it outlives the service, and a boot lies before the window's first
scrape, so no delta is taken).  ``labels`` picks the children — every
label named must match, the others (``model``) are free, and children
that match are summed; ``over`` names a second pick of the same family
to divide by (a share).  Label children stay apart here, where
``reduce.parse_prom`` sums them.  A program without the family (the
parent), or with no child that matches, has nothing to read: no value."""

import re

_SAMPLE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def children(text: str, family: str) -> list[tuple[dict, float]]:
    """``[(labels, value)]`` of the samples named ``family``."""
    out = []
    for line in text.splitlines():
        if not line.startswith(family):
            continue
        m = _SAMPLE.match(line)
        if m is None or m.group(1) != family:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        out.append((dict(_LABEL.findall(m.group(2) or "")), value))
    return out


def pick(kids: list[tuple[dict, float]], labels: dict):
    hit = [v for have, v in kids
           if all(have.get(k) == str(want) for k, want in labels.items())]
    return sum(hit) if hit else None


def read(ctx, family: str, labels: dict, over: dict | None = None,
         scale: float = 1.0):
    from mlmicroservicetemplate_tpu.utils import metrics

    body, _ = metrics.render()
    kids = children(body.decode("utf-8"), family)
    value = pick(kids, labels)
    if value is None:
        return None
    note = {"value": value}
    if over is not None:
        base = pick(kids, over)
        if not base:
            return None
        note["over"] = base
        value = value / base
    ctx.notes[f"{family}:{','.join(map(str, labels.values()))}"] = note
    return value * scale
