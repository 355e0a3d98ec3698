"""The least memory traffic one cycle's solve needs, from the snapshot's
logical shapes and the action list alone — so it counts the same work
whatever implements it.

Each action that runs has to read the cluster state once and write its
decision once.  Per action, in bytes (f32 and i32 are 4, a flag is 1):

read   nodes    allocatable, free, releasing          3 * N * R * 4
       gangs    per-task request, validity            G * T * (R * 4 + 1)
                queue, min_member, priority, order    G * 4 * 4
       running  node, gang, request, priority         P * (3 * 4 + R * 4)
       queues   quota, weight, limit, allocated,
                fair share, parent                    Q * (5 * R * 4 + 4)
write  placements per task                            G * T * 4
       victim flag and move target per running pod    P * (1 + 4)
       free pool, queue allocation                    N * R * 4 + Q * R * 4

N nodes, G gangs, T tasks per gang, P pods that hold a node, Q queues,
R resources.  Padding, temporaries and re-reads inside an action are the
implementation's, not the work's, and are not counted.
"""
from __future__ import annotations


def solve_min_bytes(shapes: dict, actions: list[str]) -> int:
    n, g, t = shapes["nodes"], shapes["gangs"], shapes["tasks_per_gang"]
    p, q, r = shapes["placed_pods"], shapes["queues"], shapes["resources"]
    read = (3 * n * r * 4 + g * t * (r * 4 + 1) + g * 4 * 4
            + p * (3 * 4 + r * 4) + q * (5 * r * 4 + 4))
    write = g * t * 4 + p * (1 + 4) + n * r * 4 + q * r * 4
    return (read + write) * len(actions)
