"""Traffic loops: ``bench/traffic/<mix>.json`` names one by its ``loop``."""
