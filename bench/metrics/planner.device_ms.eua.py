"""Device time of the planner's ``rollout`` program per plan in the EUA
replan cell, ms: read as ``planner.device_ms.replan`` reads it."""
from bench import harness

read = harness.reader("planner.device_ms.replan")
