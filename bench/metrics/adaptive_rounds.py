"""Mean adaptive rounds per selection: ``raw.rounds`` of each result.

For DASH this is the chosen guess's rounds plus filter iterations, not
the lattice's lockstep count (the lattice advances until its slowest
guess is done).  No reading for algorithms that report no rounds.
"""


def read(run):
    rounds = [int(c.out["rounds"]) for c in run.completed
              if c.out.get("rounds") is not None]
    if not rounds:
        return None
    return sum(rounds) / len(rounds)
