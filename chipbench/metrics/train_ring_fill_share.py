"""Share of the ring's stack slots that hold a token (%): valid tokens over
M·M·cap slots, from the program's record of the ring's geometry
(``Trainer.bench_record()["ring"]``). The other slots are sentinels,
sampled and masked: MH work spent on nothing. A program without the record
reports nothing."""


def read(run):
    ring = run["counters"].get("ring")
    if not ring or not ring.get("slots"):
        return None
    return 100.0 * ring["tokens"] / ring["slots"]
