"""One module per traffic ``kind``; ``run.py`` finds it by the traffic file's ``kind``."""
