"""Idle share of the device in the EUA replan cell's traced window, %:
read as ``device_idle.replan`` reads it."""
from bench import harness

read = harness.reader("device_idle.replan")
