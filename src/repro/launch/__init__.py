"""Launchers: production mesh, compile cache, train/serve drivers."""
