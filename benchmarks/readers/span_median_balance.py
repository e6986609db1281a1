"""Sum of the medians of the spans named in `plus` less the sum of the
medians of those in `minus` (ms): what spans that ran side by side saved
against running one after the other. With `plus` = the legs of an rrf
node and its fuse and `minus` = the `rrf` span itself: median leg + median
leg - (median rrf - median fuse). Medians, not a request's own spans:
`run.py` hands readers spans by name, as `client_minus_span` takes them.
Nothing unless every named span was recorded."""

from stats import median


def read(obs: dict, args: dict):
    names = list(args["plus"]) + list(args["minus"])
    samples = [obs["spans_ms"].get(name) for name in names]
    if not all(samples):
        return None
    medians = [median(s) for s in samples]
    return sum(medians[:len(args["plus"])]) - sum(medians[len(args["plus"]):])
