"""Launchers: the host mesh, the compile cache and the train/serve drivers."""
