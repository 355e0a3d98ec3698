"""Binds and moves of the window's commits onto a node that lacks the
pod's selector labels or carries a taint the pod does not tolerate:
every document posted and every commit returned is replayed through
``lib/placement_model.py`` against the cluster document.  0 on every
sound run.  It stands beside ``correct`` until ``lib/host_model.py``
knows labels and taints."""
import json

from lib.placement_model import PlacementModel


def read(run):
    model = PlacementModel(json.loads(run.cluster_json))
    first = len(run.records) - len(run.cycles)   # warm-up comes before
    count = 0
    for i, (delta, intake, commit) in enumerate(run.records):
        model.apply_doc(json.loads(delta))
        model.apply_doc(json.loads(intake))
        if i >= first:
            count += model.violations(json.loads(commit))
    return count
