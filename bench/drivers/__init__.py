"""Drivers: one per traffic generator, found by the name a traffic file gives."""
