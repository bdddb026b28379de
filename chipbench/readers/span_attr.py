"""The sum of one numeric attribute (`attr` in the metric's file) over the
program's spans of one name (`span`), times `scale` (1 where the file gives
none), per job. Where no span of that name carries the attribute, as in a
program whose spans record no resource counters, nothing is returned and
the line leaves the metric out: no value rather than a false 0."""


def read(ctx, params):
    values = [(s.get("attrs") or {}).get(params["attr"]) for s in ctx["spans"]
              if s["name"] == params["span"]]
    values = [v for v in values if v is not None]
    if not values:
        return None
    return params.get("scale", 1.0) * sum(values) / ctx["jobs"]
