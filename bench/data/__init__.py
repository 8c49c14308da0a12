"""The graphs of the deployments, made from a graph seed."""
