"""Plain float32 reference of a fleet round, independent of the program.

``models`` holds the client models (forward, weighted loss, last-layer
gradient features), ``fleet`` one synchronous FedCore round (local SGD,
features, coreset epochs on given medoids, weighted aggregation), and
``kmedoids64`` the float64 fixed-point judge of a medoid set.  Nothing
here imports the program.
"""
