"""Share of the problem-iterations a fleet call runs that belong to a
problem still iterating. Each shape bucket (power of two of the layer
count, at least 8) loops until its slowest problem stops, and a stopped
problem is computed again every iteration, so the rest is waste."""


def _bucket(layers: int) -> int:
    return max(8, 1 << max(0, layers - 1).bit_length())


def read(run):
    if run.kind != "fleet" or not run.steps:
        return None
    live = total = 0
    for step in run.steps:
        longest = {}
        members = {}
        for layers, it in zip(run.layers, step.iterations):
            b = _bucket(layers)
            longest[b] = max(longest.get(b, 0), it)
            members[b] = members.get(b, 0) + 1
            live += it
        total += sum(members[b] * longest[b] for b in members)
    return 100.0 * live / total if total else None
