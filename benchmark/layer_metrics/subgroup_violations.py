"""Gangs of the window's commits bound with a declared subgroup below
its ``min_member``, and gangs of unequal pods left pending by a commit
though they fit what it left free: every document posted and every
commit returned is replayed through ``lib/subgroup_model.py``.  0 on
every sound run.  It stands beside ``correct`` until
``lib/host_model.py`` knows subgroups and judges gangs whose pods
differ."""
import json

from lib.subgroup_model import SubgroupModel


def read(run):
    model = SubgroupModel(json.loads(run.cluster_json))
    first = len(run.records) - len(run.cycles)   # warm-up comes before
    count = 0
    for i, (delta, intake, commit) in enumerate(run.records):
        model.apply_doc(json.loads(delta))
        model.apply_doc(json.loads(intake))
        tally = model.check_commit(json.loads(commit))
        if i >= first:
            count += tally["below_quorum"] + tally["mixed_left_pending"]
    return count
