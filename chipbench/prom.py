"""Reading a Prometheus text exposition, and differencing two of them."""


def sample(text: str, name: str, **labels):
    """The value of the first sample of `name` that carries `labels`; None
    where the exposition has none."""
    for line in text.splitlines():
        if not line.startswith(name) or line[len(name):len(name) + 1] \
                not in ("{", " "):
            continue
        if all(f'{k}="{v}"' in line for k, v in labels.items()):
            return float(line.rsplit(" ", 1)[1])
    return None


def delta(scrapes: dict, first: str, last: str, name: str, **labels):
    """How far a counter moved between two named scrapes; None where either
    is missing."""
    if first not in scrapes or last not in scrapes:
        return None
    a = sample(scrapes[first]["metrics"], name, **labels)
    b = sample(scrapes[last]["metrics"], name, **labels)
    return None if a is None or b is None else b - a
