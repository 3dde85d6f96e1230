"""The chip benchmark of the OTA-DSGD system (see ``run.py``)."""
