"""Launchers: meshes, GNN step functions and cell builder, train/serve drivers."""
