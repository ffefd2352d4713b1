"""Model families: what a model *is*, one module each. A configuration
file names its family under ``family`` (absent: ``dense``) and
``lib/family.py`` finds the module here by that name. A family imports
nothing of the program."""
