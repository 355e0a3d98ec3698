"""Gangs of the window's commits bound across more than one domain of
their required topology level, and gangs of equal pods left pending by a
commit though a domain of their required level had room for all of
them: the two counts that ``lib/topology_model.py``'s ``TreeHostModel``
holds to 0 for ``correct``, over the window's cycles alone (``correct``
counts the warm-up too).  0 on every sound run."""
from lib.topology_model import window_tallies


def read(run):
    tallies = window_tallies(run)
    if not tallies:
        return None
    return sum(t["required_split"] + t["domain_left_pending"]
               for t in tallies)
